"""The gated delta rule's chunked forward and backward, on the Pallas kernels
(interpreted here) and on lax.scan, against the token-by-token recurrence of
models/causal_lm_reference.py and jax.grad of it, at ten shapes and decays.
The recurrence and its gradients are traced and run once a case, for both
paths (as test_kda.py's `_want`); test_gated_delta_rule.py holds the helpers
and everything else about the op."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops import gated_delta_kernels as gdk
from paddle_tpu.ops import kernel_config
from test_gated_delta_rule import TOLERANCE, _error, _forward_and_grads, \
    _inputs, _recurrence

CASES = [     # id, T, chunk, kwargs of _inputs
    ("t64_c16", 64, 16, {}),
    ("t64_c64", 64, 64, {}),
    ("t75_c16_ragged", 75, 16, {}),
    ("t75_c32_ragged", 75, 32, {}),
    ("t130_c128_ragged", 130, 128, {}),
    ("one_chunk_short", 9, 16, {}),
    ("g_near_zero", 48, 16, {"g_scale": 1e-4}),
    ("g_strongly_negative", 48, 16, {"g_scale": 4.0, "g_shift": 12.0}),
    ("one_head_each", 40, 16, {"hk": 3, "hv": 3}),
    ("four_value_heads_a_key_head", 40, 16, {"hk": 1, "hv": 4}),
]


_WANT = {}


def _want(name, t, kw):
    """(inputs, cotangent, the recurrence's output, its five gradients) of
    one case."""
    if name not in _WANT:
        args = _inputs(t, **kw)
        ct = jnp.asarray(np.random.RandomState(1).randn(*args[2].shape),
                         jnp.float32)
        _WANT[name] = (args, ct) + _forward_and_grads(_recurrence, args, ct)
    return _WANT[name]


@pytest.mark.parametrize("path", ["kernel", "scan"])
@pytest.mark.parametrize("name,t,chunk,kw", CASES, ids=[c[0] for c in CASES])
def test_chunked_forward_and_backward_against_the_recurrence(
        monkeypatch, path, name, t, chunk, kw):
    # two blocks of heads: the second starts from a state scratch the first
    # one left full
    monkeypatch.setitem(kernel_config.DEFAULT_TILES, "gdr",
                        dict(kernel_config.DEFAULT_TILES["gdr"], block_h=4))
    args, ct, want, want_grads = _want(name, t, kw)
    got, got_grads = _forward_and_grads(
        lambda *a: gdk.gated_delta_rule(*a, path=path, chunk=chunk), args,
        ct)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _error(got, want) < TOLERANCE
    for which, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        assert _error(a, b, floor=1e-3) < 5 * TOLERANCE, which
