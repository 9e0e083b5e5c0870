"""The architecture modules (``bench/reference/<name>.py``) as the shared
code reads them.

Golden readings: every value below was recorded on the commit before the
model description moved into the architecture modules (when
``bench/model.py``, ``bench/weights.py`` and ``bench/flops.py`` held it
for one layer kind), and must stay exactly as it was: the leaves of every
layer, the FLOP and byte counts at the published sizes, the lost
experts, and at a small size on the CPU the bits of every weight and of
the reference's final hidden states.

And an architecture whose layers differ (``two_stack``: a dense layer,
then MoE layers) plugs in as a module of its own: its weights take the
program's two-stack tree, and its reference agrees with the program's
forward pass."""
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check
from bench import flops as FL
from bench import weights as W
from bench.model import load_config, program_config, reference_module
from bench.tests import smoke, two_stack

QWEN, INTERNLM = "qwen2-moe-a2.7b.l8", "internlm2-20b.l10"
SEED = 2 ** 33 + 11

LEAVES = {
    QWEN: (8, {
        "down": ((60, 1408, 2048), 0.026650089544451305),
        "gate": ((60, 2048, 1408), 0.02209708691207961),
        "router": ((2048, 60), 0.02209708691207961),
        "shared_down": ((5632, 2048), 0.013325044772225653),
        "shared_gate": ((2048, 5632), 0.02209708691207961),
        "shared_up": ((2048, 5632), 0.02209708691207961),
        "up": ((60, 2048, 1408), 0.02209708691207961),
        "wk": ((2048, 2048), 0.02209708691207961),
        "wo": ((2048, 2048), 0.02209708691207961),
        "wq": ((2048, 2048), 0.02209708691207961),
        "wv": ((2048, 2048), 0.02209708691207961)}),
    INTERNLM: (10, {
        "ffn_down": ((16384, 6144), 0.0078125),
        "ffn_gate": ((6144, 16384), 0.01275775907699572),
        "ffn_up": ((6144, 16384), 0.01275775907699572),
        "wk": ((6144, 1024), 0.01275775907699572),
        "wo": ((6144, 6144), 0.01275775907699572),
        "wq": ((6144, 6144), 0.01275775907699572),
        "wv": ((6144, 1024), 0.01275775907699572)}),
}

# (ctx, head) -> token_flops
TOKEN_FLOPS = {
    QWEN: {(1, False): 1377763328, (1, True): 2000093184,
           (1024, False): 1444806656, (1024, True): 2067136512,
           (3328, False): 1595801600, (3328, True): 2218131456},
    INTERNLM: {(1, False): 7801651200, (1, True): 8938831872,
               (1024, False): 8053063680, (1024, True): 9190244352,
               (3328, False): 8619294720, (3328, True): 9756475392},
}
# (start, stop) -> prefill_flops
PREFILL_FLOPS = {
    QWEN: {(0, 1024): 1445155831808, (512, 2048): 2245043159040},
    INTERNLM: {(0, 1024): 8117614018560, (512, 2048): 12466331320320},
}
# attention_call on [[5, 6, 7], [10]] and on [[2048]]: (FLOPs, bytes)
ATTENTION_CALL = {
    QWEN: ((229376, 172032), (16777216, 16785408)),
    INTERNLM: ((688128, 167936), (50331648, 8413184)),
}

# at smoke size (bench.tests.smoke), seed SEED, vocabulary padded to 640,
# bfloat16: crc32 of each leaf's bytes
COMMON_CRC = {"['embed']": 2761039157, "['final_norm']": 717149004,
              "['layers']['ln1']": 634602299,
              "['layers']['ln2']": 634602299,
              "['layers']['mixer']['wo']": 3937001311,
              "['layers']['mixer']['wq']": 304752915,
              "['lm_head']": 3798187825}
CRC = {
    QWEN: {**COMMON_CRC,
           "['layers']['mixer']['wk']": 903875686,
           "['layers']['mixer']['wv']": 4204698340,
           "['layers']['moe']['down']": 2181765679,
           "['layers']['moe']['gate']": 3961785999,
           "['layers']['moe']['router']": 2713770489,
           "['layers']['moe']['shared']['w_down']": 3304220002,
           "['layers']['moe']['shared']['w_gate']": 1098476319,
           "['layers']['moe']['shared']['w_up']": 3994554141,
           "['layers']['moe']['up']": 1882169078},
    INTERNLM: {**COMMON_CRC,
               "['layers']['ffn']['w_down']": 1126849672,
               "['layers']['ffn']['w_gate']": 596701883,
               "['layers']['ffn']['w_up']": 2766499681,
               "['layers']['mixer']['wk']": 565811679,
               "['layers']['mixer']['wv']": 303305463},
}
# crc32 of check.final_hidden's float32 bytes, per precision, for one
# 40-token sequence (30 prompt tokens) with every expert live, and for
# qwen the same sequence with the experts lost with EP rank 1 masked
HIDDEN_CRC = {
    QWEN: {"f32": [2682320384, 2650729426], "f8": [4043184250, 2649968410]},
    INTERNLM: {"f32": [941872704], "f8": [2590430571]},
}


def _arch(name: str, cfg: dict):
    arch = reference_module(cfg["reference"])
    return arch, arch.shape(cfg)


@pytest.mark.parametrize("name", [QWEN, INTERNLM])
def test_layer_leaves_as_recorded(name):
    arch, s = _arch(name, load_config(name))
    layers, leaves = LEAVES[name]
    assert s.layers == layers
    for i in range(s.layers):
        assert arch.layer_leaves(s, i) == leaves


@pytest.mark.parametrize("name", [QWEN, INTERNLM])
def test_flop_and_byte_counts_as_recorded(name):
    arch, s = _arch(name, load_config(name))
    for (ctx, head), want in TOKEN_FLOPS[name].items():
        assert FL.token_flops(arch, s, ctx, head) == want
    for (start, stop), want in PREFILL_FLOPS[name].items():
        assert FL.prefill_flops(arch, s, start, stop) == want
    small, long = ATTENTION_CALL[name]
    assert arch.attention_call(s, [[5, 6, 7], [10]]) == small
    assert arch.attention_call(s, [[2048]]) == long


def test_lost_experts_as_recorded():
    arch, s = _arch(QWEN, load_config(QWEN))
    assert arch.experts_lost_with(s, 1) == list(range(32, 60))


@pytest.mark.parametrize("name", [QWEN, INTERNLM])
def test_weights_bit_for_bit(name):
    arch, s = _arch(name, smoke.config(name))
    params = W.program_params(SEED, arch, s, 640, jnp.bfloat16)
    got = {jax.tree_util.keystr(k): zlib.crc32(np.asarray(v).tobytes())
           for k, v in jax.tree_util.tree_leaves_with_path(params)}
    assert got == CRC[name]


@pytest.mark.parametrize("name", [QWEN, INTERNLM])
def test_reference_hidden_states_bit_for_bit(name):
    arch, s = _arch(name, smoke.config(name))
    toks = [int(t) for t in np.random.default_rng(7).integers(0, s.vocab,
                                                              40)]
    seqs = [check.Served(toks, 30)]
    if s.moe:
        live = np.ones((s.experts,), bool)
        live[arch.experts_lost_with(s, 1)] = False
        seqs.append(check.Served(toks, 30, live))
    for precision, want in HIDDEN_CRC[name].items():
        hs = check.final_hidden(SEED, arch, s, seqs, precision)
        assert [zlib.crc32(np.asarray(h, np.float32).tobytes())
                for h in hs] == want


# -- an architecture whose layers differ ---------------------------------------

def _program_smoke():
    """The program's qwen2-moe-a2.7b smoke configuration, with its first
    layer dense: two stacks."""
    from repro.configs import get_smoke_config
    pcfg = get_smoke_config("qwen2-moe-a2.7b")
    return dataclasses.replace(pcfg, moe=dataclasses.replace(
        pcfg.moe, first_k_dense=1))


def _two_stack_config(pcfg) -> dict:
    """A configuration file for ``two_stack`` at the sizes of ``pcfg``."""
    m = pcfg.moe
    return {"arch": "qwen2-moe-a2.7b", "hidden_size": pcfg.d_model,
            "num_attention_heads": pcfg.num_heads,
            "num_key_value_heads": pcfg.num_kv_heads,
            "head_dim": pcfg.head_dim, "num_hidden_layers": pcfg.num_layers,
            "vocab_size": pcfg.vocab_size, "rms_norm_eps": pcfg.norm_eps,
            "rope_theta": pcfg.rope_theta, "num_experts": m.num_experts,
            "num_experts_per_tok": m.top_k,
            "moe_intermediate_size": m.expert_d_ff,
            "shared_expert_intermediate_size":
                m.num_shared_experts * m.expert_d_ff,
            "first_k_dense_replace": m.first_k_dense,
            "intermediate_size": m.dense_d_ff or pcfg.d_ff,
            "deployment": {"num_dp": 2,
                           "redundant_experts": m.num_redundant_experts,
                           "drop_tokens": False}}


def test_two_stack_weights_take_the_programs_tree():
    from repro.models.model import Model
    pcfg = _program_smoke()
    model = Model(pcfg, jnp.bfloat16)
    assert [g[0] for g in model.layer_groups()] == ["dense_layers", "layers"]
    cfg = _two_stack_config(pcfg)
    s = two_stack.shape(cfg)
    params = W.program_params(SEED, two_stack, s, model.vpad, jnp.bfloat16)
    W.check_layout(params, model.param_specs())
    # the module's own program configuration builds the same tree
    mine = Model(program_config(cfg, two_stack, s), jnp.bfloat16)
    W.check_layout(params, mine.param_specs())
    # and counts each stack's layers as their kind
    dense = two_stack.matmul_params(s, 0)
    assert dense == 2 * 256 * 256 + 2 * 256 * 128 + 3 * 256 * 512
    assert FL.token_flops(two_stack, s, 1, False) == 2 * (
        dense + two_stack.matmul_params(s, 1)) + 2 * 4 * 4 * 64


# float32 on both sides from the same weights: the two forward passes
# differ only in the order of their sums, 1.7e-6 at most here on logits
# up to 1.25 (CPU); 1e-4 leaves room for that, as the harness's exact
# runs do (test_bench_run.EXACT), and a layer computed wrong is off by
# whole units (1.5 with the dense layer's FFN left out)
EXACT = 1e-4


def test_two_stack_reference_matches_the_programs_forward_pass():
    from repro.models.model import Model
    cfg = _two_stack_config(_program_smoke())
    s = two_stack.shape(cfg)
    model = Model(program_config(cfg, two_stack, s), jnp.float32)
    params = W.program_params(SEED, two_stack, s, model.vpad, jnp.float32)
    toks = np.random.default_rng(3).integers(0, s.vocab, 24).astype(np.int32)
    logits, _, _ = model.logits_full(params, {"tokens": jnp.asarray(
        toks[None])})
    got = np.asarray(logits[0, :-1, : s.vocab])
    (h,) = check.final_hidden(SEED, two_stack, s,
                              [check.Served(list(toks), 1)], "f32",
                              jnp.float32)
    head = W.lm_head(W.seed_key(SEED), s, jnp.float32)
    want = (np.asarray(h, np.float64) @ np.asarray(head, np.float64))[
        : len(toks) - 1]
    assert np.abs(want).max() > 0.05
    np.testing.assert_allclose(got, want, rtol=0, atol=EXACT)
