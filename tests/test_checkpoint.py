"""Checkpoint manager: roundtrip, torn writes, schedules."""
import io
import json
import os
import random
import shutil
import threading
import zipfile
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_latest, \
    save_checkpoint
from repro.core import distributions as D


@pytest.fixture()
def tmpdir(tmp_path):
    return str(tmp_path / "ckpt")


def _tree(key=0):
    k = jax.random.PRNGKey(key)
    return {"a": jax.random.normal(k, (4, 8)),
            "nested": {"b": jnp.arange(6).reshape(2, 3),
                       "c": [jnp.ones(3), jnp.zeros(2)]}}


def test_roundtrip(tmpdir):
    tree = _tree()
    save_checkpoint(tmpdir, 7, tree, {"note": "x"})
    out = restore_latest(tmpdir, tree)
    assert out is not None
    restored, step, meta = out
    assert step == 7 and meta["note"] == "x"
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_wins_and_torn_write_skipped(tmpdir):
    t1, t2 = _tree(1), _tree(2)
    save_checkpoint(tmpdir, 10, t1)
    save_checkpoint(tmpdir, 20, t2)
    # corrupt the newest (simulate preemption mid-write)
    path = os.path.join(tmpdir, "step_0000000020", "arrays.npz")
    with open(path, "r+b") as f:
        f.seek(100)
        f.write(b"\x00" * 64)
    restored, step, _ = restore_latest(tmpdir, t1)
    assert step == 10, "corrupted checkpoint must be skipped"


def _member_span(npz: str, arr) -> tuple:
    """(start, end) of ``arr``'s stored ``.npy`` bytes inside ``npz``."""
    b = io.BytesIO()
    np.lib.format.write_array(b, np.asarray(arr))
    with open(npz, "rb") as f:
        start = f.read().find(b.getvalue())
    assert start >= 0
    return start, start + len(b.getvalue())


def _flip_last_leaf_byte(path, tree):
    npz = os.path.join(path, "arrays.npz")
    last = jax.tree_util.tree_leaves(tree)[-1]
    _, end = _member_span(npz, last)
    with open(npz, "r+b") as f:
        f.seek(end - 1)
        byte = f.read(1)
        f.seek(end - 1)
        f.write(bytes([byte[0] ^ 0xFF]))


def _truncate_mid_member(path, tree):
    npz = os.path.join(path, "arrays.npz")
    start, end = _member_span(npz, jax.tree_util.tree_leaves(tree)[0])
    with open(npz, "r+b") as f:
        f.truncate((start + end) // 2)


def _remove_manifest(path, tree):
    os.remove(os.path.join(path, "manifest.json"))


def _wrong_manifest_crc(path, tree):
    name = os.path.join(path, "manifest.json")
    with open(name) as f:
        manifest = json.load(f)
    info = next(iter(manifest["arrays"].values()))
    info["crc32"] = (info["crc32"] + 1) % 2 ** 32
    with open(name, "w") as f:
        json.dump(manifest, f)


@pytest.mark.parametrize("damage", [_flip_last_leaf_byte,
                                    _truncate_mid_member, _remove_manifest,
                                    _wrong_manifest_crc],
                         ids=["last_leaf_byte", "truncated", "no_manifest",
                              "manifest_crc"])
def test_damaged_newest_falls_back_to_older(tmpdir, damage):
    """Any fault in the newest checkpoint, found while its leaves are read,
    discards it and restores the next-older one whole."""
    t1, t2 = _tree(1), _tree(2)
    save_checkpoint(tmpdir, 10, t1)
    save_checkpoint(tmpdir, 20, t2)
    damage(os.path.join(tmpdir, "step_0000000020"), t2)
    restored, step, _ = restore_latest(tmpdir, t1)
    assert step == 10
    for a, b in zip(jax.tree_util.tree_leaves(t1),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shape_unlike_template_falls_back_to_older(tmpdir):
    save_checkpoint(tmpdir, 10, _tree(1))
    wide = _tree(2)
    wide["a"] = jnp.zeros((8, 4))
    save_checkpoint(tmpdir, 20, wide)
    assert restore_latest(tmpdir, _tree())[1] == 10


def _manifest_bytes(path) -> int:
    with open(os.path.join(path, "manifest.json")) as f:
        arrays = json.load(f)["arrays"]
    return sum(int(np.prod(a["shape"])) * np.dtype(a["dtype"]).itemsize
               for a in arrays.values())


def test_restore_reads_each_leaf_once_bit_exact(tmpdir):
    """A restore of an intact checkpoint reads the manifest's array bytes
    exactly once, and hands back every leaf bit for bit with the
    template's shape and dtype (int32, a Fortran-ordered and a 0-d leaf
    among them)."""
    from repro.checkpoint import manager
    tree = {"w": jax.random.normal(jax.random.PRNGKey(3), (5, 7)),
            "i": jnp.arange(12, dtype=jnp.int32).reshape(3, 4),
            "f": np.asfortranarray(np.arange(6.0).reshape(2, 3)),
            "s": np.array(-2.5, np.float32)}
    save_checkpoint(tmpdir, 4, tree)
    total = _manifest_bytes(os.path.join(tmpdir, "step_0000000004"))
    before = manager.restore_bytes()
    restored, step, _ = restore_latest(tmpdir, tree)
    assert step == 4
    assert manager.restore_bytes() - before == total
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        a, b = np.asarray(a), np.asarray(b)
        assert b.dtype == a.dtype and b.shape == a.shape
        assert np.ascontiguousarray(b).tobytes() == \
            np.ascontiguousarray(a).tobytes()


def test_async_write(tmpdir):
    tree = _tree()
    th = save_checkpoint(tmpdir, 3, tree, blocking=False)
    th.join()
    assert restore_latest(tmpdir, tree)[1] == 3


def test_writer_keeps_newest_two(tmpdir):
    """After each completed write only the newest two checkpoints remain,
    pruned by the writer thread; a half-written one is neither counted nor
    removed, and the newest restores."""
    from repro.checkpoint import manager
    os.makedirs(tmpdir)
    torn = os.path.join(tmpdir, ".tmp_torn")
    os.makedirs(torn)
    for step in (3, 8, 12, 30):
        th = save_checkpoint(tmpdir, step, _tree(step), blocking=False)
        th.join(timeout=60)
        assert not th.is_alive()
    kept = sorted(d for d in os.listdir(tmpdir) if d.startswith("step_"))
    assert manager.KEEP == 2
    assert kept == ["step_0000000012", "step_0000000030"]
    assert os.path.isdir(torn)
    restored, step, _ = restore_latest(tmpdir, _tree())
    assert step == 30
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(_tree(30)["a"]))


@pytest.mark.parametrize("zip64_at", [None, 1], ids=["plain", "zip64"])
def test_archive_is_a_valid_npz(tmpdir, monkeypatch, zip64_at):
    """The written ``arrays.npz`` is a stored zip that ``zipfile`` accepts
    (every member's CRC checks) and ``np.load`` reads leaf for leaf, with
    zip64 extras for sizes and offsets (forced from byte 1 in ``zip64``)."""
    from repro.checkpoint import manager
    if zip64_at is not None:
        monkeypatch.setattr(manager, "_ZIP64_AT", zip64_at)
    tree = {"f32": jax.random.normal(jax.random.PRNGKey(1), (3, 17)),
            "bf16": jnp.linspace(-2, 2, 10, dtype=jnp.bfloat16),
            "int": jnp.arange(12, dtype=jnp.int32).reshape(4, 3),
            "empty": jnp.zeros((0, 5)),
            "scalar": np.array(-1.5, np.float32),
            "fortran": np.asfortranarray(np.arange(12.0).reshape(3, 4))}
    save_checkpoint(tmpdir, 2, tree)
    npz = os.path.join(tmpdir, "step_0000000002", "arrays.npz")
    with zipfile.ZipFile(npz) as z:
        assert z.testzip() is None
        assert all(i.compress_type == zipfile.ZIP_STORED
                   for i in z.infolist())
    with np.load(npz) as z:
        assert sorted(z.files) == sorted(tree)
        for key, leaf in tree.items():
            a, b = np.asarray(leaf), z[key]
            assert b.shape == a.shape
            # numpy names bfloat16 in a .npy header only as raw V2
            assert b.dtype == (a.dtype if key != "bf16" else np.dtype("V2"))
            assert np.ascontiguousarray(b).tobytes() == \
                np.ascontiguousarray(a).tobytes()


def test_crc32_combine_matches_crc32_of_concatenation():
    from repro.checkpoint.manager import crc32_combine
    rng = random.Random(7)
    data = rng.randbytes(70_000)
    cuts = [0, 1, len(data) - 1, len(data)] + \
        [rng.randrange(len(data) + 1) for _ in range(60)]
    for cut in cuts:
        for start in (0, cut // 2, cut):   # the first part may be empty
            a, b = data[start:cut], data[cut:]
            assert crc32_combine(zlib.crc32(a), zlib.crc32(b), len(b)) \
                == zlib.crc32(a + b), (start, cut)
    assert crc32_combine(zlib.crc32(b""), zlib.crc32(b""), 0) == 0


def test_save_bytes_counts_each_save_once(tmpdir):
    from repro.checkpoint import manager
    before = manager.save_bytes()
    save_checkpoint(tmpdir, 1, _tree(1))
    once = manager.save_bytes() - before
    assert once == _manifest_bytes(os.path.join(tmpdir, "step_0000000001"))
    th = save_checkpoint(tmpdir, 2, _tree(2), blocking=False)
    th.join(timeout=60)
    assert not th.is_alive()
    assert manager.save_bytes() - before == 2 * once


def test_async_save_returns_after_the_host_copy_alone(tmpdir, monkeypatch):
    """With the writer held in its first member, the caller's save has
    returned having run no crc32, and a jitted step that donates the saved
    state overwrites it; after ``wait`` the checkpoint restores the state
    at the save bit for bit."""
    from repro.checkpoint import manager
    held, entered = threading.Event(), threading.Event()
    real_member, real_crc32 = manager._write_member, zlib.crc32
    crc_threads = []

    def held_member(*a):
        entered.set()
        assert held.wait(60)
        return real_member(*a)

    def crc32(*a):
        crc_threads.append(threading.get_ident())
        return real_crc32(*a)

    monkeypatch.setattr(manager, "_write_member", held_member)
    monkeypatch.setattr(manager.zlib, "crc32", crc32)
    state = _tree(5)
    at_save = jax.tree_util.tree_map(lambda x: np.array(x, copy=True),
                                     state)
    mgr = CheckpointManager(directory=tmpdir, dist=D.constrained_for(),
                            policy="none")
    mgr.save(9, state)
    assert entered.wait(60) and mgr._writer.is_alive()
    assert threading.get_ident() not in crc_threads
    step = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x * 3 + 1, t),
                   donate_argnums=0)
    state = jax.block_until_ready(step(state))
    held.set()
    mgr.wait()
    assert crc_threads and threading.get_ident() not in crc_threads
    monkeypatch.setattr(manager.zlib, "crc32", real_crc32)
    restored, step_no, _ = restore_latest(tmpdir, at_save)
    assert step_no == 9
    for a, b in zip(jax.tree_util.tree_leaves(at_save),
                    jax.tree_util.tree_leaves(restored)):
        assert b.dtype == a.dtype and b.shape == a.shape
        assert np.asarray(b).tobytes() == a.tobytes()


def _mgr(tmpdir, policy, **kw):
    return CheckpointManager(directory=tmpdir, dist=D.constrained_for(),
                             policy=policy, step_time_hours=0.01,
                             total_steps=1000, async_write=False, **kw)


def test_dp_schedule_nonuniform(tmpdir):
    """DP intervals at pod age 0 start short and lengthen."""
    mgr = _mgr(tmpdir, "dp")
    first = mgr._next_ckpt_step
    tree = _tree()
    mgr.save(first, tree)
    second_gap = mgr._next_ckpt_step - first
    assert second_gap >= first, "DP gaps should lengthen as hazard decays"


def test_young_daly_schedule_uniform(tmpdir):
    mgr = _mgr(tmpdir, "young_daly")
    g1 = mgr._next_ckpt_step
    mgr.save(g1, _tree())
    g2 = mgr._next_ckpt_step - g1
    assert g1 == g2, "Young-Daly is periodic"


def test_emergency_save_is_blocking_and_counted(tmpdir):
    mgr = _mgr(tmpdir, "dp")
    mgr.on_preemption_warning(42, _tree())
    assert mgr.n_emergency == 1
    assert restore_latest(tmpdir, _tree())[1] == 42


def test_restart_recomputes_schedule(tmpdir):
    mgr = _mgr(tmpdir, "dp")
    before = mgr._next_ckpt_step
    mgr.on_restart(pod_age_hours=0.0, resumed_step=500)
    after = mgr._next_ckpt_step
    assert after > 500, "schedule must re-anchor at the resumed step"
    assert after - 500 <= before * 2 + 1


def test_policy_none(tmpdir):
    mgr = _mgr(tmpdir, "none")
    assert not mgr.should_checkpoint(10 ** 6)
