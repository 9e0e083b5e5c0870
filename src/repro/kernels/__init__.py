"""Pallas TPU kernels for the serving hot spots.

Every kernel has a pure-jnp oracle in :mod:`repro.kernels.ref` (the
semantic ground truth for tests) and a jit'd public wrapper in
:mod:`repro.kernels.ops` with a ``use_pallas`` fallback switch — on CPU
the wrappers default to the oracle, on TPU they compile natively.

Kernels:

* ``flash_prefill``   — causal GQA prefill, online softmax in VMEM.
* ``paged_attention`` — one-token decode over a paged KV pool.
* ``router_topk``     — mask -> softmax -> top-k -> renormalize; the
  §3.4 failure mask is a kernel *input* (recovery = data write).
* ``ssm_scan``        — Mamba selective scan.
* ``expert_ffn``      — grouped SwiGLU FFN over a pre-built capacity
  buffer (building block, kept for the dense-scatter path).
* ``moe_fused``       — the fused MoE pipeline: token dispatch ->
  grouped SwiGLU FFN -> weighted combine in one kernel, fed by a single
  jnp sort pass (``moe_group_tokens``).  Selected end-to-end via
  ``ModelConfig.moe_impl`` ('fused', 'gather_psum_fused', 'a2a_fused')
  or ``EngineConfig.moe_impl``; the routing tables it consumes come from
  ``MoERuntime``, so ReviveMoE recovery (replica drop / expert mask)
  stays a data mutation with zero recompiles.

Every kernel passes ``pltpu.CompilerParams`` directly.
``paged_attention``, ``moe_fused``
and ``decode_megakernel`` are compiled for a described v5e at
qwen2-moe-a2.7b widths by ``tests/test_tpu_compile.py``; paged attention
also at internlm2-20b's and minicpm3-4b's (MLA) widths.
"""
