"""The timed path broken underneath, one fault at a time: each run
must come out not correct. The harness is the one a chip run uses,
past its look for a chip."""
import jax.numpy as jnp
import pytest

from chipbench.tests import rehearsal


def _token(mp):
    import repro.serving.engine as eng
    orig = eng.sample_tokens
    mp.setattr(eng, "sample_tokens",
               lambda k, lg, t: (orig(k, lg, t) + 1) % 512)


def _kv_state(mp):
    import repro.models.kv_cache as kv
    mp.setattr(kv, "write_kv", lambda k, v, kn, vn, pos: (k, v))


def _cold_output(mp):
    import repro.kernels.ops as ops
    orig = ops.fused_cold_ffn

    def dropped(*a, **kw):
        y, idx = orig(*a, **kw)
        return jnp.zeros_like(y), idx
    mp.setattr(ops, "fused_cold_ffn", dropped)


def _half_batch(mp):
    import repro.core.sparse_ffn as sf
    orig = sf.ffn_hybrid

    def half(params, x, *a, active_mask=None, **kw):
        if active_mask is not None:
            keep = jnp.arange(x.shape[0]) < max(x.shape[0] // 2, 1)
            active_mask = active_mask & keep
        return orig(params, x, *a, active_mask=active_mask, **kw)
    mp.setattr(sf, "ffn_hybrid", half)


@pytest.mark.parametrize("fault", [_token, _kv_state, _cold_output,
                                   _half_batch],
                         ids=["token", "kv_state", "cold_output",
                              "half_batch"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    r = rehearsal.run()
    assert not r["correct"], r["checks"]
