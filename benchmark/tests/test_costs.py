"""The bytes function against the live arrays at a small capacity."""
import pytest

from harness import costs


def _nbytes(tree):
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_step_bytes_match_the_live_arrays():
    import jax

    from dragonboat_tpu.ops import colocated, types

    G, P, W, M, E, O, B = 32, 3, 16, 8, 4, 32, 4
    state = types.make_state(G, P, W)
    host = types.make_inbox(G, M, E)
    pending = types.make_inbox(G, P * B, E)
    assert _nbytes(state) == costs.state_bytes(G, P, W)
    assert _nbytes(host) == costs.inbox_bytes(G, M, E)
    assert _nbytes(pending) == costs.inbox_bytes(G, P * B, E)
    combo = jax.numpy.zeros((G, 4), jax.numpy.int32)
    new_state, out = jax.eval_shape(
        lambda s, h, p, c: colocated._assemble_and_step(
            s, h, p, c, out_capacity=O), state, host, pending, combo)
    assert _nbytes(out) == costs.out_bytes(G, P, M + P * B, E, O)
    assert _nbytes(new_state) == _nbytes(state)
    total = (2 * _nbytes(state) + _nbytes(host) + _nbytes(pending)
             + combo.size * 4 + _nbytes(out))
    assert costs.colocated_step_bytes(G, P, W, M, E, O, B) == total


def test_shipped_geometry_and_peaks():
    from harness.manifest import Manifest

    man = Manifest()
    eng = man.config("base-1k3")["engine"]
    need = costs.colocated_step_bytes(**eng)
    assert 10e6 < need < 30e6       # ~16 MB: ~20 us at 819 GB/s
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("TPU v99")
