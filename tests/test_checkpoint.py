"""Checkpoint system: atomicity, rotation, restore fidelity, elastic load."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpointer as ck
from repro.checkpoint.manager import CheckpointManager

KEY = jax.random.key(0)


def _tree(seed=0):
    k = jax.random.key(seed)
    return {"w": jax.random.normal(k, (64, 32)),
            "nested": {"b": jnp.arange(17, dtype=jnp.int32),
                       "scale": jnp.float32(3.5)},
            "stack": jax.random.normal(jax.random.fold_in(k, 1), (4, 8, 8))}


def test_save_restore_bit_identical(tmp_path):
    t = _tree()
    ck.save(tmp_path, 10, t)
    got, step = ck.restore(tmp_path)
    assert step == 10
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_uncommitted_checkpoint_ignored(tmp_path):
    t = _tree()
    ck.save(tmp_path, 1, t)
    ck.save(tmp_path, 2, t)
    # corrupt checkpoint 2: simulate a crash mid-save (remove commit marker)
    (pathlib.Path(tmp_path) / "step_2" / "_COMMITTED").unlink()
    assert ck.latest_step(tmp_path) == 1
    _, step = ck.restore(tmp_path)
    assert step == 1


def test_rotation_keeps_last_k(tmp_path):
    t = _tree()
    for s in range(1, 8):
        ck.save(tmp_path, s, t, keep=3)
    assert ck.all_steps(tmp_path) == [5, 6, 7]


def test_manager_resume_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), save_every=5)
    t = _tree(3)
    assert mgr.maybe_save(3, t) is None           # not on the cadence
    assert mgr.maybe_save(5, t) is not None
    got, step = mgr.resume()
    assert step == 5
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(t["w"]))


def test_elastic_restore_new_sharding(tmp_path):
    """Checkpoint saved unsharded restores onto a different mesh layout."""
    t = _tree()
    ck.save(tmp_path, 1, t)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = {"w": NamedSharding(mesh, P("data", None)),
          "nested": {"b": NamedSharding(mesh, P()),
                     "scale": NamedSharding(mesh, P())},
          "stack": NamedSharding(mesh, P(None, None, None))}
    got, _ = ck.restore(tmp_path, shardings=sh)
    assert got["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(t["w"]))


def test_corrupt_checkpoint_falls_back_to_previous(tmp_path):
    """A committed-but-damaged save (bit rot / truncation that still
    renamed) fails CRC verification and restore() walks back to the
    newest older checkpoint instead of returning garbage."""
    import pytest
    ck.save(tmp_path, 1, _tree(1))
    ck.save(tmp_path, 2, _tree(2))
    shard = pathlib.Path(tmp_path) / "step_2" / "shard_0.npz"
    shard.write_bytes(shard.read_bytes()[:-40] + b"\x00" * 40)   # bit rot
    with pytest.warns(RuntimeWarning, match="corrupt"):
        got, step = ck.restore(tmp_path)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.asarray(_tree(1)["w"]))
    # an explicit step request raises instead of silently falling back
    with pytest.raises(ck.CheckpointCorruptError):
        ck.restore(tmp_path, 2)


def test_all_checkpoints_corrupt_raises(tmp_path):
    import pytest
    ck.save(tmp_path, 1, _tree())
    (pathlib.Path(tmp_path) / "step_1" / "meta.json").write_text("{oops")
    with pytest.raises(ck.CheckpointCorruptError):
        with pytest.warns(RuntimeWarning):
            ck.restore(tmp_path)


def test_version_and_schema_rejection(tmp_path):
    import json
    import pytest
    ck.save(tmp_path, 1, _tree(), schema="my-schema")
    # wrong schema tag
    with pytest.raises(ValueError, match="schema"):
        ck.restore(tmp_path, 1, expect_schema="other-schema")
    got, _ = ck.restore(tmp_path, 1, expect_schema="my-schema")
    assert "w" in got
    # a format newer than this reader is refused, never half-parsed
    meta_p = pathlib.Path(tmp_path) / "step_1" / "meta.json"
    meta = json.loads(meta_p.read_text())
    meta["version"] = ck.FORMAT_VERSION + 1
    meta_p.write_text(json.dumps(meta))
    with pytest.raises(ValueError, match="newer"):
        ck.restore(tmp_path, 1)


def test_v1_checkpoint_still_loads(tmp_path):
    """Pre-PR-8 checkpoints (no version/CRC fields) remain readable."""
    import json
    ck.save(tmp_path, 1, _tree())
    meta_p = pathlib.Path(tmp_path) / "step_1" / "meta.json"
    meta = json.loads(meta_p.read_text())
    del meta["version"], meta["shard_crc"], meta["schema"]
    meta_p.write_text(json.dumps(meta))
    got, step = ck.restore(tmp_path)
    assert step == 1 and "w" in got


def test_largevis_save_load_roundtrip(tmp_path):
    """LargeVis.save/load: versioned checkpoint (not a pickle), bitwise
    embedding + graph round trip, working samplers/key/cfg — loaded
    models transform() bitwise-identically to the original."""
    from repro import LargeVis, LargeVisConfig
    cfg = LargeVisConfig(n_neighbors=6, n_trees=2, n_explore_iters=1,
                         window=16, perplexity=4.0, samples_per_node=60,
                         batch_size=64, steps_per_dispatch=10)
    x = np.asarray(jax.random.normal(KEY, (128, 8)), np.float32)
    m = LargeVis(cfg=cfg).fit(x, jax.random.key(1))
    m.save(tmp_path / "model")
    m2 = LargeVis.load(tmp_path / "model")
    for f in ("y", "knn_idx", "knn_dist", "weights", "x"):
        np.testing.assert_array_equal(np.asarray(getattr(m.result_, f)),
                                      np.asarray(getattr(m2.result_, f)))
    assert m2.result_.cfg == m.result_.cfg
    q = x[:5] + 0.01
    np.testing.assert_array_equal(np.asarray(m.transform(q)),
                                  np.asarray(m2.transform(q)))
    # wrong schema: loading some other checkpoint as a model is refused
    ck.save(tmp_path / "other", 0, _tree())
    import pytest
    with pytest.raises(ValueError, match="schema"):
        LargeVis.load(tmp_path / "other")


def test_grad_compression_bounds_and_ef():
    from repro.optim.grad_compress import (compress, compression_ratio,
                                           compressed_grads_with_ef,
                                           decompress)
    g = {"a": jax.random.normal(KEY, (1000,)),
         "b": jax.random.normal(jax.random.fold_in(KEY, 1), (64, 64)) * 10}
    q = compress(g, KEY)
    deq = decompress(q, g)
    for orig, rec in zip(jax.tree.leaves(g), jax.tree.leaves(deq)):
        scale = float(jnp.max(jnp.abs(orig)))
        # per-block max error <= scale/127 (one quantization unit + rounding)
        assert float(jnp.max(jnp.abs(orig - rec))) <= scale / 127.0 + 1e-6
    assert compression_ratio(g) < 0.27
    # error feedback: sum over steps of (deq) converges to sum of grads
    ef = None
    acc_deq = jax.tree.map(jnp.zeros_like, g)
    for i in range(20):
        deq, ef = compressed_grads_with_ef(g, ef, jax.random.fold_in(KEY, i))
        acc_deq = jax.tree.map(lambda a, d: a + d, acc_deq, deq)
    # EF guarantees accumulated quantized grads track accumulated true grads
    for orig, acc in zip(jax.tree.leaves(g), jax.tree.leaves(acc_deq)):
        drift = float(jnp.max(jnp.abs(acc / 20.0 - orig)))
        scale = float(jnp.max(jnp.abs(orig)))
        assert drift <= scale / 127.0 + 1e-5, drift
