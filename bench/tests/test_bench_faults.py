"""A run with the timed path broken underneath comes out not correct,
once for each fault a serving cell can have: a token altered where it is
produced; a step that leaves its state (the KV cache) unchanged; half of
the batch left out; the exchange between the two EP ranks left out; and,
in the cell with a fault, a revive that leaves the lost experts in the
gating function (only the tokens served after the revive are wrong)."""
import pytest

from bench.tests import cpu_run, smoke


def _alter_token(monkeypatch):
    import repro.serving.executor as X
    orig = X.sample

    def sample(logits, params, step=0):
        return (orig(logits, params, step=step) + 1) % 512
    monkeypatch.setattr(X, "sample", sample)


def _keep_state(monkeypatch):
    import repro.models.attention as A
    monkeypatch.setattr(A, "gqa_write_token",
                        lambda pools, page, k, v: pools)


def _half_batch(monkeypatch):
    import jax.numpy as jnp

    import repro.kernels.ops as O
    orig = O.paged_attention

    def paged_attention(q, *a, **kw):
        out = orig(q, *a, **kw)
        half = q.shape[0] // 2
        return out.at[half:].set(jnp.zeros_like(out[half:]))
    monkeypatch.setattr(O, "paged_attention", paged_attention)


def _no_exchange(monkeypatch):
    import repro.models.moe as M
    orig = M.dispatch_compute_combine

    def local_only(x, weights, phys, alive, *a, e_local, **kw):
        # only the first EP rank's slots contribute
        return orig(x, weights, phys, alive & (phys < e_local // 2), *a,
                    e_local=e_local, **kw)
    monkeypatch.setattr(M, "dispatch_compute_combine", local_only)


def _revive_unmasked(monkeypatch):
    # the revived runtime keeps every expert in the gating function, the
    # lost ones included (before the fault none is lost, so nothing
    # changes there)
    import jax.numpy as jnp

    import repro.core.expert_map as EM
    orig = EM.ExpertMap.runtime

    def runtime(self):
        rt = orig(self)
        return rt._replace(expert_mask=jnp.ones_like(rt.expert_mask))
    monkeypatch.setattr(EM.ExpertMap, "runtime", runtime)


@pytest.mark.parametrize("cell,fault", [
    (smoke.QWEN, _alter_token),
    (smoke.INTERNLM, _keep_state),
    (smoke.INTERNLM, _half_batch),
    (smoke.QWEN, _no_exchange),
])
def test_broken_path_is_not_correct(monkeypatch, cell, fault):
    fault(monkeypatch)
    out = cpu_run.run(cell, monkeypatch, seconds=3.0)
    assert out["correct"] is False
    # the gap the cell compares opens past its limit (the token counts
    # alone would not make a smoke-size run incorrect)
    gaps = {k: c for k, c in out["checks"].items() if "gap" in k}
    assert gaps and any(c["value"] > c["limit"] for c in gaps.values())


def test_wrong_revived_runtime_fails_the_after_phase(monkeypatch):
    """A fault only in the revived expert runtime leaves the tokens served
    before the fault right and fails the numbers of the after phase."""
    _revive_unmasked(monkeypatch)
    out = cpu_run.run(smoke.QWEN, monkeypatch, seconds=10.0)
    c = out["checks"]
    assert out["correct"] is False
    assert c["mean_logit_gap.before"]["value"] <= c[
        "mean_logit_gap.before"]["limit"]
    assert c["mean_logit_gap.after"]["value"] > c[
        "mean_logit_gap.after"]["limit"]
