"""The checkpoint subsystem: atomic step-numbered writes, manifest discovery,
retention, config/treedef validation — and the sampler serializable-state
contract swept over the whole registry (save -> restore into a fresh template
-> continue must be bitwise-equal to never having round-tripped).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    CheckpointManager,
    config_fingerprint,
    restore_checkpoint,
    save_checkpoint,
)
from repro.core import samplers


# ---------------------------------------------------------------------------
# checkpointer.py satellites: strict dtype, treedef read-back, atomic sidecar
# ---------------------------------------------------------------------------


def test_restore_rejects_dtype_mismatch(tmp_path):
    """Dtype drift raises like shape drift does — no silent astype."""
    f = save_checkpoint(str(tmp_path / "c"), {"a": jnp.zeros((3,), jnp.float32)})
    with pytest.raises(ValueError, match="dtype"):
        restore_checkpoint(f, {"a": np.zeros((3,), np.float64)})
    with pytest.raises(ValueError, match="dtype"):
        restore_checkpoint(f, {"a": np.zeros((3,), np.int32)})


@pytest.mark.parametrize(
    "dtype", [jnp.bfloat16, jnp.float8_e4m3fn, jnp.float8_e5m2]
)
def test_ml_dtypes_round_trip_bitwise(tmp_path, dtype):
    """bf16 params and fp8 delta rows survive npz, which keeps only the
    width of these dtypes; a different dtype of the same width still raises."""
    x = (jnp.arange(24, dtype=jnp.float32) / 7.0 - 1.5).astype(dtype).reshape(4, 6)
    f = save_checkpoint(str(tmp_path / "c"), {"x": x, "y": jnp.ones((2,))})
    got = restore_checkpoint(f, {"x": jnp.zeros_like(x), "y": jnp.zeros((2,))})
    assert got["x"].dtype == x.dtype
    np.testing.assert_array_equal(
        np.asarray(got["x"]).view(np.uint8), np.asarray(x).view(np.uint8)
    )
    other = jnp.float16 if dtype == jnp.bfloat16 else (
        jnp.float8_e5m2 if dtype == jnp.float8_e4m3fn else jnp.float8_e4m3fn
    )
    with pytest.raises(ValueError, match="dtype"):
        restore_checkpoint(f, {"x": jnp.zeros(x.shape, other), "y": jnp.zeros((2,))})


def test_restore_compares_saved_treedef(tmp_path):
    """The .treedef.txt sidecar is actually read back: a template with the
    same leaf count/shapes/dtypes but a different STRUCTURE must raise
    (before this fix, only leaf count was checked)."""
    f = save_checkpoint(
        str(tmp_path / "c"), {"a": jnp.zeros((3,)), "b": jnp.ones((3,))}
    )
    with pytest.raises(ValueError, match="treedef"):
        restore_checkpoint(f, {"a": jnp.zeros((3,)), "z": jnp.ones((3,))})
    with pytest.raises(ValueError, match="treedef"):
        restore_checkpoint(f, (jnp.zeros((3,)), jnp.ones((3,))))


def test_save_publishes_atomically_no_stray_tmp(tmp_path):
    """Both the .npz and the .treedef.txt go through tmp + os.replace: after
    a successful save the directory holds exactly the two published files."""
    save_checkpoint(str(tmp_path / "c"), {"a": jnp.zeros((2,))})
    names = sorted(os.listdir(tmp_path))
    assert names == ["c.npz", "c.treedef.txt"]
    assert not any(n.endswith(".tmp") for n in names)


# ---------------------------------------------------------------------------
# CheckpointManager
# ---------------------------------------------------------------------------


def _state(x=0.0):
    return {"w": jnp.full((4,), x, jnp.float32), "t": jnp.asarray(0, jnp.int32)}


def test_manager_save_latest_restore(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"))
    assert mgr.latest() is None
    assert mgr.read_manifest() is None
    mgr.save(_state(1.0), step=2)
    mgr.save(_state(2.0), step=4)
    assert mgr.latest() == 4
    manifest = mgr.read_manifest()
    assert manifest["step"] == 4
    assert manifest["steps"] == [2, 4]
    assert manifest["format"] == 1
    assert "jax" in manifest["versions"] and "numpy" in manifest["versions"]
    got = mgr.restore(_state())
    np.testing.assert_array_equal(np.asarray(got["w"]), np.full((4,), 2.0))
    # explicit older step is still reachable while retained
    got2 = mgr.restore(_state(), step=2)
    np.testing.assert_array_equal(np.asarray(got2["w"]), np.full((4,), 1.0))


def test_manager_restore_or_init(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"))
    template = _state(7.0)
    state, step = mgr.restore_or_init(template)
    assert step == 0 and state is template  # fresh: the template itself
    mgr.save(_state(3.0), step=5)
    state, step = mgr.restore_or_init(_state())
    assert step == 5
    np.testing.assert_array_equal(np.asarray(state["w"]), np.full((4,), 3.0))


def test_manager_retention_keep_last(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"), keep_last=2)
    for step in (1, 2, 3, 4):
        mgr.save(_state(float(step)), step=step)
    manifest = mgr.read_manifest()
    assert manifest["steps"] == [3, 4]
    files = sorted(os.listdir(tmp_path / "ck"))
    assert files == [
        "manifest.json",
        "state_00000003.npz", "state_00000003.treedef.txt",
        "state_00000004.npz", "state_00000004.treedef.txt",
    ]
    assert mgr.latest() == 4


def test_manager_config_fingerprint_guard(tmp_path):
    fp_a = config_fingerprint({"rounds": 10, "seed": 0})
    fp_b = config_fingerprint({"rounds": 20, "seed": 0})
    assert fp_a != fp_b
    # stable across key ordering
    assert fp_a == config_fingerprint({"seed": 0, "rounds": 10})
    CheckpointManager(str(tmp_path / "ck"), fingerprint=fp_a).save(_state(), step=1)
    with pytest.raises(ValueError, match="fingerprint"):
        CheckpointManager(str(tmp_path / "ck"), fingerprint=fp_b).restore(_state())
    # same fingerprint resumes fine
    CheckpointManager(str(tmp_path / "ck"), fingerprint=fp_a).restore(_state())


def test_manager_treedef_hash_guard(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(_state(), step=1)
    with pytest.raises(ValueError, match="treedef"):
        mgr.restore({"w": jnp.zeros((4,), jnp.float32), "u": jnp.asarray(0, jnp.int32)})


def test_manager_manifest_is_commit_point(tmp_path):
    """A checkpoint file without a manifest entry is unreachable (the torn-
    write story): drop a stray step file next to a committed one and latest()
    still reports only what the manifest committed."""
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(_state(1.0), step=2)
    # stray uncommitted files (as if the process died before the manifest write)
    save_checkpoint(mgr.checkpoint_path(9), _state(9.0))
    assert mgr.latest() == 2
    got, step = mgr.restore_or_init(_state())
    assert step == 2
    np.testing.assert_array_equal(np.asarray(got["w"]), np.full((4,), 1.0))
    # and a manifest pointing at a deleted file falls back to an older step
    mgr.save(_state(3.0), step=4)
    os.remove(mgr.checkpoint_path(4))
    assert mgr.latest() == 2


# ---------------------------------------------------------------------------
# wait_for_next: the blocking read side of the train-to-serve hand-off
# ---------------------------------------------------------------------------


def test_wait_for_next_returns_newly_committed_step(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"))
    assert mgr.wait_for_next(0, timeout=0.05) is None  # nothing ever committed
    mgr.save(_state(1.0), step=2)
    assert mgr.wait_for_next(0, timeout=0.05) == 2
    # already-seen steps don't satisfy the wait
    assert mgr.wait_for_next(2, timeout=0.05) is None
    # timeout=0 is the non-blocking one-shot check
    assert mgr.wait_for_next(0, timeout=0.0) == 2
    assert mgr.wait_for_next(2, timeout=0.0) is None


def test_wait_for_next_against_concurrent_writer(tmp_path):
    """A reader polling ``wait_for_next`` while a writer thread publishes
    boundaries must see a strictly increasing step sequence and restore
    complete state at EVERY step it observes — the atomic-manifest commit
    point means a torn step is never visible, only a possibly-stale one."""
    import threading

    path = str(tmp_path / "ck")
    steps = [2, 4, 6, 8, 10]
    writer_mgr = CheckpointManager(path, keep_last=len(steps))

    def writer():
        import time

        for s in steps:
            writer_mgr.save(_state(float(s)), step=s)
            time.sleep(0.02)

    reader_mgr = CheckpointManager(path)
    t = threading.Thread(target=writer)
    t.start()
    seen = []
    after = 0
    while after < steps[-1]:
        step = reader_mgr.wait_for_next(after, timeout=5.0, poll_interval=0.005)
        assert step is not None, f"writer stalled after {seen}"
        assert step > after  # monotone: never a stale or repeated boundary
        got = reader_mgr.restore(_state(), step=step)
        np.testing.assert_array_equal(  # never torn: value matches its step
            np.asarray(got["w"]), np.full((4,), float(step))
        )
        seen.append(step)
        after = step
    t.join()
    assert seen[-1] == steps[-1]
    assert set(seen) <= set(steps)


# ---------------------------------------------------------------------------
# Sampler serializable-state contract: full registry round-trip sweep
# ---------------------------------------------------------------------------


def _advance(s, state, key, rounds, n):
    """Drive `rounds` rounds of the sampler life cycle, returning the state
    trajectory's probabilities so the test compares behaviour, not just leaves."""
    fb_full = jax.random.uniform(jax.random.PRNGKey(17), (n,), minval=0.1, maxval=1.0)
    probs = []
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        p = s.probabilities(state)
        draw = s.sample_from(p, sub)
        state = s.update(state, draw, fb_full * draw.mask)
        probs.append(np.asarray(p))
    return state, key, probs


@pytest.mark.parametrize("name", sorted(samplers._REGISTRY))
def test_sampler_state_survives_checkpoint_round_trip(name, tmp_path):
    """Every registered sampler's state obeys the serializable-state contract:
    3 rounds -> save -> restore into a FRESH ``init()`` template -> 5 more
    rounds must be bitwise-equal (probabilities and every state leaf) to the
    same 8 rounds without the round trip."""
    n, k = 16, 4
    s = samplers.make_sampler(name, n=n, budget=k)
    key = jax.random.PRNGKey(0)

    state, key_mid, _ = _advance(s, s.init(), key, 3, n)
    samplers.assert_serializable_state(state)

    mgr = CheckpointManager(str(tmp_path / name))
    mgr.save(state, step=3)
    restored, step = mgr.restore_or_init(s.init())  # fresh-template restore
    assert step == 3

    cont, _, probs_cont = _advance(s, restored, key_mid, 5, n)
    ref, _, probs_ref = _advance(s, state, key_mid, 5, n)
    np.testing.assert_array_equal(np.stack(probs_cont), np.stack(probs_ref))
    for a, b in zip(jax.tree_util.tree_leaves(cont), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_assert_serializable_state_rejects_python_scalars():
    samplers.assert_serializable_state(
        samplers.SamplerState(
            stats=jnp.zeros(3), aux=jnp.zeros(3), t=jnp.asarray(0, jnp.int32)
        )
    )
    with pytest.raises(TypeError, match="not an array"):
        samplers.assert_serializable_state(
            samplers.SamplerState(stats=jnp.zeros(3), aux=jnp.zeros(3), t=0)
        )
    with pytest.raises(ValueError, match="no array leaves"):
        samplers.assert_serializable_state({})
