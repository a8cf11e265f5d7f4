"""Distributed checkpointing with model-driven (DP) scheduling.

Mechanics:
  * pytrees are flattened to path->array dicts and written as .npz with a
    JSON manifest carrying shapes/dtypes/CRC32s and user metadata;
  * writes are atomic (tmp dir + rename) and optionally asynchronous: the
    calling thread makes the device->host copy and nothing else (the next
    step donates the state); a writer thread checksums each leaf once on
    its host buffer, leaves spread over a few threads, and writes it
    straight from that buffer into a stored zip64 ``.npz`` whose member
    CRCs are derived from the leaves' (``save_bytes`` counts the bytes) -
    on TPU fleets the same split hides the object-store upload;
  * ``restore_latest`` scans the directory and returns the newest intact
    checkpoint, reading each leaf once from the file straight into the
    host array it returns and checking its CRC32 there (``restore_bytes``
    counts the bytes read) - a half-written checkpoint from a preempted
    pod is skipped, which is exactly the failure mode the paper's 30 s
    warning window creates;
  * after each completed write the writer thread keeps the newest ``KEEP``
    completed checkpoints and deletes older ones, so a long job's saves do
    not fill the disk (a 16B-class MoE shard's state is ~7 GB a save).

Scheduling: ``CheckpointManager`` consumes the paper's DP policy
(repro.core.policies.checkpointing).  Given the fitted preemption model, the
measured per-step time and the measured checkpoint cost delta, it computes
the optimal *non-uniform* schedule in units of steps and answers
``should_checkpoint(step)``.  A Young-Daly or fixed-interval schedule can be
selected for baselines (EXPERIMENTS.md compares them).
"""
from __future__ import annotations

import dataclasses
import functools
import io
import json
import math
import os
import shutil
import struct
import tempfile
import threading
import time
import zipfile
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional

import jax
import numpy as np

from .. import obs
from ..core.policies import checkpointing as ckpt_policy
from ..core.policies import young_daly


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------

KEEP = 2   # completed checkpoints kept after each write

def _flatten(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out[key] = np.asarray(leaf)
    return out


def _unflatten_like(template, flat: dict):
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in paths:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        arr = flat[key]
        if arr.shape != tuple(leaf.shape):
            raise ValueError(f"{key}: shape {arr.shape} != {leaf.shape}")
        leaves.append(arr.astype(leaf.dtype, copy=False))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def save_checkpoint(directory: str, step: int, tree, metadata: Optional[dict]
                    = None, *, blocking: bool = True) -> threading.Thread:
    """Atomic (tmp+rename) checkpoint write; returns the writer thread.

    The calling thread makes the host copy (``jax.device_get``) and nothing
    else; the checksums, the manifest and the write run on the writer
    thread, which ``blocking`` joins before returning."""
    os.makedirs(directory, exist_ok=True)
    flat = _flatten(jax.device_get(tree))  # host copy is synchronous
    when = time.time()

    def write():
        global _save_bytes
        tmp = tempfile.mkdtemp(dir=directory, prefix=".tmp_")
        try:
            with ThreadPoolExecutor(_CRC_THREADS) as pool:
                crcs = _write_npz(os.path.join(tmp, "arrays.npz"), flat,
                                  pool.map(_checksummed, flat.values()),
                                  when)
            with _save_lock:
                _save_bytes += sum(v.nbytes for v in flat.values())
            manifest = {
                "step": int(step),
                "time": when,
                "metadata": metadata or {},
                "arrays": {k: {"shape": list(v.shape), "dtype": str(v.dtype),
                               "crc32": crcs[k]}
                           for k, v in flat.items()},
            }
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
            final = os.path.join(directory, f"step_{int(step):010d}")
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        prune(directory)

    t = threading.Thread(target=write, daemon=True)
    t.start()
    if blocking:
        t.join()
    return t


_CRC_THREADS = min(8, os.cpu_count() or 1)
_save_lock = threading.Lock()
_save_bytes = 0


def save_bytes() -> int:
    """Leaf bytes checksummed and written by saves in this process: a save
    adds the total size of its manifest's arrays, once."""
    return _save_bytes


def _checksummed(v: np.ndarray) -> tuple[np.ndarray, int]:
    """``v`` in C order (a copy only if it is not C-contiguous already)
    and the CRC32 of its bytes, computed on that buffer."""
    arr = v if v.flags.c_contiguous else np.array(v, order="C")
    return arr, zlib.crc32(arr)


# ---------------------------------------------------------------------------
# the .npz writer: a stored-only zip64 archive of .npy 1.0 members, each
# array's buffer written once, its CRC32 derived from the leaf's
# ---------------------------------------------------------------------------

_ZIP64_AT = 0xFFFFFFFF   # sizes and offsets from here on go in zip64 extras
_ZIP_LOCAL = struct.Struct("<IHHHHHIIIHH")
_ZIP_CENTRAL = struct.Struct("<IHHHHHHIIIHHHHHII")
_ZIP64_END = struct.Struct("<IQHHIIQQQQ")
_ZIP64_LOCATOR = struct.Struct("<IIQI")
_ZIP_END = struct.Struct("<IHHHHIIH")
_UTF8_NAME = 0x800


def _gf2_times(mat, vec: int) -> int:
    out, i = 0, 0
    while vec:
        if vec & 1:
            out ^= mat[i]
        vec >>= 1
        i += 1
    return out


def _gf2_square(mat) -> tuple:
    return tuple(_gf2_times(mat, row) for row in mat)


@functools.cache
def _crc_zeros(k: int) -> tuple:
    """The operator that feeds ``2**k`` zero bytes through a CRC32
    register, as 32 columns over GF(2): squared up from one zero bit."""
    if k:
        return _gf2_square(_crc_zeros(k - 1))
    op = (0xEDB88320,) + tuple(1 << i for i in range(31))
    for _ in range(3):   # 2, 4, 8 bits
        op = _gf2_square(op)
    return op


def crc32_combine(crc1: int, crc2: int, len2: int) -> int:
    """``zlib.crc32(a + b)`` from ``crc1 = zlib.crc32(a)``, ``crc2 =
    zlib.crc32(b)`` and ``len2 = len(b)``: a port of zlib's
    ``crc32_combine``, which Python's ``zlib`` does not expose."""
    for k in range(len2.bit_length()):
        if len2 >> k & 1:
            crc1 = _gf2_times(_crc_zeros(k), crc1)
    return crc1 ^ crc2


def _u32(v: int) -> int:
    return v if v < _ZIP64_AT else 0xFFFFFFFF


def _zip64_extra(*values: int) -> bytes:
    return struct.pack(f"<HH{len(values)}Q", 1, 8 * len(values), *values) \
        if values else b""


def _write_member(f, key: str, arr: np.ndarray, crc: int, dos: tuple) -> bytes:
    """Writes ``arr`` (C-contiguous; ``crc`` the CRC32 of its bytes) as the
    stored member ``key.npy`` at ``f``'s position: the local header, the
    ``.npy`` 1.0 header, then the array's buffer, once.  Returns the
    member's central directory record."""
    offset = f.tell()
    head = io.BytesIO()
    np.lib.format.write_array_header_1_0(
        head, np.lib.format.header_data_from_array_1_0(arr))
    head = head.getvalue()
    size = len(head) + arr.nbytes
    crc = crc32_combine(zlib.crc32(head), crc, arr.nbytes)
    name = (key + ".npy").encode()
    sizes = (size, size) if size >= _ZIP64_AT else ()
    far = (offset,) if offset >= _ZIP64_AT else ()
    version = 45 if sizes or far else 20
    local = _zip64_extra(*sizes)
    f.write(_ZIP_LOCAL.pack(0x04034B50, version, _UTF8_NAME, 0, *dos, crc,
                            _u32(size), _u32(size), len(name), len(local))
            + name + local)
    f.write(head)
    f.write(arr)
    central = _zip64_extra(*sizes, *far)
    return _ZIP_CENTRAL.pack(
        0x02014B50, 3 << 8 | version, version, _UTF8_NAME, 0, *dos, crc,
        _u32(size), _u32(size), len(name), len(central), 0, 0, 0,
        0o600 << 16, _u32(offset)) + name + central


def _write_npz(path: str, flat: dict, checksummed, when: float) -> dict:
    """Writes ``flat``'s leaves, as ``checksummed`` yields them in order
    (``(array, crc32)``), into a new ``.npz`` at ``path``; returns each
    key's CRC32."""
    t = time.localtime(when)
    dos = (t.tm_hour << 11 | t.tm_min << 5 | t.tm_sec // 2,
           max(t.tm_year - 1980, 0) << 9 | t.tm_mon << 5 | t.tm_mday)
    crcs, records = {}, []
    with open(path, "wb") as f:
        for key, (arr, crc) in zip(flat, checksummed):
            records.append(_write_member(f, key, arr, crc, dos))
            crcs[key] = crc
        start = f.tell()
        f.write(b"".join(records))
        end, n = f.tell(), len(records)
        f.write(_ZIP64_END.pack(0x06064B50, 44, 3 << 8 | 45, 45, 0, 0, n, n,
                                end - start, start))
        f.write(_ZIP64_LOCATOR.pack(0x07064B50, 0, end, 1))
        f.write(_ZIP_END.pack(0x06054B50, 0, 0, min(n, 0xFFFF),
                              min(n, 0xFFFF), _u32(end - start),
                              _u32(start), 0))
    return crcs


def prune(directory: str, keep: int = KEEP) -> None:
    """Delete all but the newest ``keep`` completed checkpoints.  A
    ``step_`` directory is complete once renamed into place (the rename is
    atomic); half-written ``.tmp_`` directories are not counted."""
    done = sorted(d for d in os.listdir(directory) if d.startswith("step_"))
    for d in done[:-keep]:
        shutil.rmtree(os.path.join(directory, d), ignore_errors=True)


_LOCAL_HEADER = struct.Struct("<4s22xHH")   # signature, name and extra sizes
_restore_bytes = 0


def restore_bytes() -> int:
    """Leaf bytes read from checkpoint files into arrays by restores in
    this process: a restore of one intact checkpoint adds the total size
    of its manifest's arrays, once."""
    return _restore_bytes


def _read_member(f, info: zipfile.ZipInfo) -> tuple[np.ndarray, int]:
    """Reads one ``.npy`` member that ``np.savez`` stored uncompressed
    straight from the open ``.npz`` file into a new array; returns it with
    the CRC32 of its C-order bytes, computed on that buffer."""
    global _restore_bytes
    if info.compress_type != zipfile.ZIP_STORED or info.flag_bits & 1:
        raise ValueError(f"{info.filename}: not a stored member")
    f.seek(info.header_offset)
    raw = f.read(_LOCAL_HEADER.size)
    if len(raw) != _LOCAL_HEADER.size:
        raise ValueError(f"{info.filename}: short local header")
    sig, n_name, n_extra = _LOCAL_HEADER.unpack(raw)
    if sig != b"PK\x03\x04":
        raise ValueError(f"{info.filename}: bad local header")
    start = info.header_offset + _LOCAL_HEADER.size + n_name + n_extra
    f.seek(start)
    version = np.lib.format.read_magic(f)
    if version != (1, 0):   # what np.savez writes for these arrays
        raise ValueError(f"{info.filename}: .npy version {version}")
    try:
        shape, fortran, dtype = np.lib.format.read_array_header_1_0(f)
    except Exception as e:  # the header is parsed as a literal: on torn
        # bytes numpy raises whatever its tokenizer does
        raise ValueError(f"{info.filename}: bad .npy header") from e
    if dtype.hasobject:
        raise ValueError(f"{info.filename}: object array")
    nbytes = dtype.itemsize * math.prod(shape)
    if f.tell() - start + nbytes != info.file_size:
        raise ValueError(f"{info.filename}: header and member size disagree")
    buf = np.empty(nbytes, np.uint8)
    if f.readinto(buf) != nbytes:
        raise ValueError(f"{info.filename}: short read")
    _restore_bytes += nbytes
    arr = buf.view(dtype).reshape(shape, order="F" if fortran else "C")
    # the manifest's CRC is of the C-order bytes, which the file holds
    # unless the array was saved Fortran-ordered
    return arr, zlib.crc32(np.ascontiguousarray(arr).tobytes() if fortran
                           else buf)


def _read_checkpoint(path: str, template) -> tuple:
    """(tree, step, metadata) of the checkpoint in ``path``: one pass over
    the manifest's arrays, each read once and CRC-checked.  Raises on any
    fault: a missing or unreadable file or member, a short read, a CRC that
    disagrees with the manifest, a shape that disagrees with ``template``."""
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    flat = {}
    with open(os.path.join(path, "arrays.npz"), "rb") as f:
        with zipfile.ZipFile(f) as z:
            members = {i.filename: i for i in z.infolist()}
        for key, info in manifest["arrays"].items():
            arr, crc = _read_member(f, members[key + ".npy"])
            if crc != info["crc32"]:
                raise ValueError(f"{key}: CRC32 disagrees with the manifest")
            flat[key] = arr
    return (_unflatten_like(template, flat), manifest["step"],
            manifest["metadata"])


def restore_latest(directory: str, template) -> Optional[tuple]:
    """Returns (tree, step, metadata) of the newest intact checkpoint."""
    if not os.path.isdir(directory):
        return None
    steps = sorted((d for d in os.listdir(directory) if d.startswith("step_")),
                   reverse=True)
    with obs.span(obs.CKPT_RESTORE):
        for d in steps:
            try:
                return _read_checkpoint(os.path.join(directory, d), template)
            except (OSError, ValueError, KeyError, zipfile.BadZipFile):
                continue  # torn write (e.g. preempted mid-checkpoint) - skip
    return None


# ---------------------------------------------------------------------------
# model-driven scheduling
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CheckpointManager:
    """Owns the checkpoint schedule + IO for a training run on preemptible
    pods.

    policy: "dp" (the paper, non-uniform), "young_daly", "fixed", "none".
    Times are in hours of *pod age*; steps are mapped through the measured
    step time (EMA-updated online via ``observe_step_time``).
    """
    directory: str
    dist: Any                               # preemption model (core.distributions)
    policy: str = "dp"
    delta_hours: float = 1.0 / 60.0         # measured checkpoint write cost
    step_time_hours: float = 1.0 / 3600.0   # seed; EMA-updated
    total_steps: int = 1000
    pod_age_hours: float = 0.0              # age of the pod at run start
    grid_dt: float = 1.0 / 60.0
    async_write: bool = True
    fixed_interval_steps: int = 100

    def __post_init__(self):
        self._tables = None
        self._next_ckpt_step: Optional[int] = None
        self._last_ckpt_step = 0
        self._pod_start_step = 0   # global step at which the current pod began
        self._writer: Optional[threading.Thread] = None
        self.n_saved = 0
        self.n_emergency = 0
        self._recompute()

    # -- schedule -----------------------------------------------------------
    def _steps_per_grid(self) -> float:
        return max(self.grid_dt / max(self.step_time_hours, 1e-9), 1.0)

    def _recompute(self):
        with obs.span(obs.CKPT_PLAN):
            if self.policy == "dp":
                remaining_h = (self.total_steps - self._last_ckpt_step) \
                    * self.step_time_hours
                job_steps = max(int(round(remaining_h / self.grid_dt)), 1)
                # the DP table V/K covers EVERY remaining length
                # j <= job_steps, so restarts reuse it (the paper: "we
                # precompute the checkpointing schedule of jobs of different
                # lengths") - only solve when no table covers the need (e.g.
                # step time grew)
                if self._tables is None or \
                        self._tables.V.shape[0] - 1 < job_steps:
                    delta_steps = max(
                        int(round(self.delta_hours / self.grid_dt)), 1)
                    self._tables = ckpt_policy.solve(
                        self.dist, job_steps, grid_dt=self.grid_dt,
                        delta_steps=delta_steps)
            self._plan_next()

    def _plan_next(self):
        step = self._last_ckpt_step
        if self.policy == "none":
            self._next_ckpt_step = None
        elif self.policy == "fixed":
            self._next_ckpt_step = step + self.fixed_interval_steps
        elif self.policy == "young_daly":
            mttf = young_daly.mttf_from_initial_rate(self.dist)
            tau_h = float(young_daly.interval(self.delta_hours, mttf))
            self._next_ckpt_step = step + max(
                int(round(tau_h / max(self.step_time_hours, 1e-9))), 1)
        else:  # dp
            # pod age counts only steps run on THIS pod (a restart resets it)
            age_h = self.pod_age_hours + \
                (step - self._pod_start_step) * self.step_time_hours
            remaining = self.total_steps - step
            rem_grid = max(int(round(remaining * self.step_time_hours
                                     / self.grid_dt)), 1)
            rem_grid = min(rem_grid, self._tables.V.shape[0] - 1)
            interval_grid = self._tables.interval_steps(
                rem_grid, int(round(age_h / self.grid_dt)))
            steps = max(int(round(interval_grid * self.grid_dt
                                  / max(self.step_time_hours, 1e-9))), 1)
            self._next_ckpt_step = step + steps

    # -- runtime hooks --------------------------------------------------------
    def observe_step_time(self, seconds: float, ema: float = 0.1):
        h = seconds / 3600.0
        self.step_time_hours = (1 - ema) * self.step_time_hours + ema * h

    def should_checkpoint(self, step: int) -> bool:
        return self._next_ckpt_step is not None and \
            step >= self._next_ckpt_step

    def save(self, step: int, tree, metadata=None, *, emergency: bool = False):
        with obs.span(obs.CKPT_SAVE, step=step, emergency=emergency):
            self.wait()  # one in-flight write at a time
            meta = dict(metadata or {})
            meta["policy"] = self.policy
            meta["emergency"] = emergency
            self._writer = save_checkpoint(
                self.directory, step, tree, meta,
                blocking=not self.async_write or emergency)
            self._last_ckpt_step = step
            self.n_saved += 1
            if emergency:
                self.n_emergency += 1
            self._plan_next()

    def on_preemption_warning(self, step: int, tree, metadata=None):
        """The provider's 30 s warning: flush an emergency checkpoint NOW."""
        self.save(step, tree, metadata, emergency=True)

    def wait(self):
        """Block until the in-flight checkpoint write (if any) is on disk."""
        if self._writer is not None and self._writer.is_alive():
            with obs.span(obs.CKPT_WAIT):
                self._writer.join()

    def restore(self, template):
        self.wait()
        return restore_latest(self.directory, template)

    def on_restart(self, *, pod_age_hours: float = 0.0, resumed_step: int = 0):
        """Resume on a fresh pod: re-anchor ages and recompute the schedule
        (the paper recomputes E[M*(J_remaining, 0)] after every failure)."""
        self.pod_age_hours = pod_age_hours
        self._last_ckpt_step = resumed_step
        self._pod_start_step = resumed_step
        self._recompute()
