"""Token-dataset loader: ctypes bindings over the native ``nxd_data`` C++
library, with a bit-identical pure-numpy fallback.

This is the framework's data pipeline (the role of MpDeviceLoader +
DistributedSampler + the HDF5 readers in the reference's training harnesses,
``tp_zero1_llama2_7b_hf_pretrain.py:192-216``): a flat tokenized corpus is
chunked into ``seq_len+1``-token samples, shuffled per epoch with a
seed-deterministic Fisher-Yates (splitmix64, identical in C++ and Python),
round-robin sharded across DP ranks, and prefetched on background threads
(native path).  ``ids``/``labels`` come out already shifted.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import weakref
from typing import Iterator, Optional, Tuple

import numpy as np

from neuronx_distributed_tpu.resilience.faults import fault_point
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

_MAGIC = 0x5444584E  # "NXDT"
_VERSION = 1
_DTYPES = {1: np.uint16, 2: np.int32}
_DTYPE_CODES = {np.dtype(np.uint16): 1, np.dtype(np.int32): 2}

_CSRC = os.path.join(os.path.dirname(__file__), "csrc", "loader.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")

_lib = None
_lib_tried = False


def _lib_path() -> str:
    """The library is named by the CONTENT of its one source file: a build
    of another ``loader.cpp`` — a stale ``_build/*.so`` that came along
    with a copied tree, whatever its mtime — is simply not this file."""
    with open(_CSRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libnxd_data.{digest}.so")


def _build_native(lib_path: str) -> Optional[str]:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # build to a per-pid temp name then rename atomically: N DP processes on
    # one host may race to build the same .so
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
           _CSRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
        return lib_path
    except (subprocess.SubprocessError, FileNotFoundError, OSError) as e:
        logger.warning("native data loader build failed (%s); using numpy fallback", e)
        if os.path.exists(tmp):
            os.unlink(tmp)
        return None


def loader_backend() -> str:
    """``"native"`` when the C++ library serves the loaders of this
    process, ``"numpy"`` when the bit-identical fallback does."""
    return "native" if _load_native() is not None else "numpy"


def _load_native():
    """Compile (once per source content) and load the native library; None
    if unavailable."""
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    path = _lib_path()
    if not os.path.exists(path):
        path = _build_native(path)
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        # e.g. a concurrently-built half-written .so; numpy fallback instead
        logger.warning("loading native data loader failed (%s); using numpy fallback", e)
        return None
    lib.nxd_open.restype = ctypes.c_void_p
    lib.nxd_open.argtypes = [ctypes.c_char_p]
    lib.nxd_close.argtypes = [ctypes.c_void_p]
    lib.nxd_num_tokens.restype = ctypes.c_uint64
    lib.nxd_num_tokens.argtypes = [ctypes.c_void_p]
    lib.nxd_num_chunks.restype = ctypes.c_uint64
    lib.nxd_num_chunks.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.nxd_loader_create.restype = ctypes.c_void_p
    lib.nxd_loader_create.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint32,
        ctypes.c_uint32, ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32]
    lib.nxd_loader_destroy.argtypes = [ctypes.c_void_p]
    lib.nxd_loader_num_batches.restype = ctypes.c_uint64
    lib.nxd_loader_num_batches.argtypes = [ctypes.c_void_p]
    lib.nxd_loader_set_epoch.argtypes = [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64]
    lib.nxd_loader_next.restype = ctypes.c_int64
    lib.nxd_loader_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32)]
    lib.nxd_pack_assign.restype = ctypes.c_int64
    lib.nxd_pack_assign.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32)]
    _lib = lib
    return _lib


def native_pack_assign(lengths: np.ndarray, seq_len: int,
                       window: int) -> Optional[Tuple[np.ndarray, int]]:
    """First-fit row assignment via the native library (``nxd_pack_assign``
    in ``csrc/loader.cpp``); ``None`` ONLY when the native path is
    unavailable — callers fall back to the bit-identical Python loop
    (``data.packing._assign_rows_py``).  Invalid input (a piece longer than
    ``seq_len``, which no assignment can place) raises rather than being
    conflated with unavailability: the fallback must never silently run a
    workload the native path rejected."""
    lib = _load_native()
    if lib is None:
        return None
    lengths = np.ascontiguousarray(lengths, np.int32)
    out = np.empty(len(lengths), np.int32)
    n_rows = lib.nxd_pack_assign(
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_int64(len(lengths)), ctypes.c_int32(int(seq_len)),
        ctypes.c_int32(int(window)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if n_rows < 0:
        raise ValueError(
            f"pack_assign: invalid input (seq_len={seq_len}, window={window}, "
            f"max piece length {int(lengths.max()) if len(lengths) else 0}) — "
            "every piece must satisfy 0 <= length <= seq_len"
        )
    return out, int(n_rows)


# ---------------------------------------------------------------------------
# file format
# ---------------------------------------------------------------------------


def write_token_file(path: str, tokens: np.ndarray) -> None:
    """Write a flat token array as an NXDT file (uint16 when the vocab fits,
    int32 otherwise)."""
    tokens = np.ascontiguousarray(tokens).reshape(-1)
    if tokens.size and tokens.min() < 0:
        raise ValueError("token ids must be non-negative (found negative values)")
    if tokens.dtype not in (np.uint16, np.int32):
        tokens = tokens.astype(np.int32 if tokens.max(initial=0) > 65535 else np.uint16)
    code = _DTYPE_CODES[tokens.dtype]
    head32 = np.array([_MAGIC, _VERSION, code, 0], np.uint32)
    with open(path, "wb") as f:
        f.write(head32.tobytes())
        f.write(np.uint64(tokens.size).tobytes())
        f.write(tokens.tobytes())


def read_token_file(path: str) -> np.ndarray:
    """Read an NXDT file back into a flat numpy array (host-side utility)."""
    with open(path, "rb") as f:
        head32 = np.frombuffer(f.read(16), np.uint32)
        if head32[0] != _MAGIC or head32[1] != _VERSION:
            raise ValueError(f"{path} is not an NXDT token file")
        n = int(np.frombuffer(f.read(8), np.uint64)[0])
        return np.frombuffer(f.read(), _DTYPES[int(head32[2])], count=n)


# ---------------------------------------------------------------------------
# deterministic shuffle shared with C++
# ---------------------------------------------------------------------------

_M64 = (1 << 64) - 1


def _splitmix64(state: int) -> Tuple[int, int]:
    state = (state + 0x9E3779B97F4A7C15) & _M64
    z = state
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    return z ^ (z >> 31), state


def _shuffled_chunks(total: int, seed: int, epoch: int) -> np.ndarray:
    """Fisher-Yates identical to the C++ ``build_order``."""
    order = np.arange(total, dtype=np.uint64)
    state = (seed + 0x51ED2700 * (epoch + 1)) & _M64
    for i in range(total, 1, -1):
        r, state = _splitmix64(state)
        j = r % i
        order[i - 1], order[j] = order[j], order[i - 1]
    return order


class TokenDataset:
    """Handle over an NXDT token file (native mmap when available)."""

    def __init__(self, path: str):
        self.path = path
        self._lib = _load_native()
        self._handle = None
        self._np_tokens = None
        self._loaders: "weakref.WeakSet" = weakref.WeakSet()
        if self._lib is not None:
            self._handle = self._lib.nxd_open(path.encode())
            if not self._handle:
                raise ValueError(f"failed to open token file {path}")
            self.num_tokens = int(self._lib.nxd_num_tokens(self._handle))
        else:
            self._np_tokens = read_token_file(path)
            self.num_tokens = int(self._np_tokens.size)

    @property
    def is_native(self) -> bool:
        return self._handle is not None

    def num_chunks(self, seq_len: int) -> int:
        if self.num_tokens < seq_len + 1:
            return 0
        return (self.num_tokens - 1) // seq_len

    def max_token_id(self) -> int:
        """Largest token id in the file (one streaming mmap scan, cached —
        never a resident copy of the corpus, whichever loader path is
        active)."""
        if not hasattr(self, "_max_token"):
            if self._np_tokens is not None:
                data = self._np_tokens
            else:
                with open(self.path, "rb") as f:
                    head32 = np.frombuffer(f.read(16), np.uint32)
                    if head32[0] != _MAGIC or head32[1] != _VERSION:
                        raise ValueError(f"{self.path} is not an NXDT token file")
                    n = int(np.frombuffer(f.read(8), np.uint64)[0])
                data = np.memmap(self.path, _DTYPES[int(head32[2])], mode="r",
                                 offset=24, shape=(n,))
            self._max_token = int(data.max()) if data.size else 0
        return self._max_token

    def validate_vocab(self, vocab_size: int, what: str = "model") -> None:
        """Fail loudly when the file holds ids outside ``[0, vocab_size)`` —
        an out-of-range id otherwise trains to a silent NaN loss (the
        vocab-parallel CE's psum-MAX eats the bad one-hot).  One shared
        check for every launcher."""
        if self.max_token_id() >= vocab_size:
            raise ValueError(
                f"data file {self.path} contains token id {self.max_token_id()} "
                f">= {what} vocab_size {vocab_size}; rebuild the data or pick "
                "a larger-vocab config (out-of-range ids train to NaN)"
            )

    def close(self):
        if self._handle is not None:
            # destroy live loaders FIRST: their prefetch threads read the
            # dataset's mmap, so nxd_close before nxd_loader_destroy is a
            # use-after-free (segfaulted under GC ordering in the wild)
            for loader in list(self._loaders):
                loader.close()
            self._lib.nxd_close(self._handle)
            self._handle = None

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass


class TokenDataLoader:
    """Iterates ``{"ids": [B, S], "labels": [B, S]}`` int32 batches for one
    DP rank.  Deterministic across restarts: ``(seed, epoch)`` fixes the
    order, ``skip_batches`` resumes mid-epoch (the reference's
    consumed-batch skip, ``run_llama_nxd.py:233-244``)."""

    def __init__(
        self,
        dataset: TokenDataset,
        batch_size: int,
        seq_len: int,
        dp_rank: int = 0,
        dp_size: int = 1,
        seed: int = 0,
        prefetch_depth: int = 4,
        num_threads: int = 2,
    ):
        if dp_rank >= dp_size:
            raise ValueError(f"dp_rank {dp_rank} >= dp_size {dp_size}")
        self.ds = dataset
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.dp_rank = dp_rank
        self.dp_size = dp_size
        self.seed = seed
        self.epoch = 0
        self._cursor = 0
        self._loader = None
        if dataset.is_native:
            lib = dataset._lib
            self._loader = lib.nxd_loader_create(
                dataset._handle, batch_size, seq_len, dp_rank, dp_size, seed,
                prefetch_depth, num_threads)
            if not self._loader:
                raise ValueError("native loader creation failed")
            dataset._loaders.add(self)  # dataset.close() tears us down first
            self.num_batches = int(lib.nxd_loader_num_batches(self._loader))
        else:
            # globally uniform count (min share across ranks) so every dp
            # rank yields the same number of batches — mirrors loader.cpp
            total = dataset.num_chunks(seq_len)
            self.num_batches = (total // dp_size) // batch_size

    def set_epoch(self, epoch: int, skip_batches: int = 0) -> None:
        """Reshuffle for ``epoch`` and reset the cursor; call before each
        epoch (both paths are single-shot between calls).  ``skip_batches``
        resumes mid-epoch."""
        self.epoch = epoch
        self._cursor = skip_batches
        if self._loader is not None:
            self.ds._lib.nxd_loader_set_epoch(self._loader, epoch, skip_batches)

    def _iter_native(self) -> Iterator[dict]:
        lib = self.ds._lib
        out = np.empty((self.batch_size, self.seq_len + 1), np.int32)
        ptr = out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        while True:
            fault_point("data/next_batch", epoch=self.epoch, rank=self.dp_rank)
            got = lib.nxd_loader_next(self._loader, ptr)
            if got < 0:
                return
            yield {"ids": out[:, :-1].copy(), "labels": out[:, 1:].copy()}

    def _iter_numpy(self) -> Iterator[dict]:
        # single-shot per set_epoch, matching the native path: once the epoch
        # is exhausted, further iteration yields nothing until set_epoch
        total = self.ds.num_chunks(self.seq_len)
        order = _shuffled_chunks(total, self.seed, self.epoch)
        mine = order[self.dp_rank::self.dp_size][: self.num_batches * self.batch_size]
        toks = self.ds._np_tokens
        n = self.seq_len
        while self._cursor < self.num_batches:
            fault_point("data/next_batch", epoch=self.epoch, rank=self.dp_rank)
            b = self._cursor
            self._cursor += 1
            chunk_ids = mine[b * self.batch_size:(b + 1) * self.batch_size]
            batch = np.stack(
                [toks[int(c) * n:int(c) * n + n + 1].astype(np.int32) for c in chunk_ids]
            )
            yield {"ids": batch[:, :-1], "labels": batch[:, 1:]}

    def __iter__(self) -> Iterator[dict]:
        if self._loader is not None:
            return self._iter_native()
        return self._iter_numpy()

    def __len__(self) -> int:
        return self.num_batches

    def close(self):
        if self._loader is not None:
            self.ds._lib.nxd_loader_destroy(self._loader)
            self._loader = None

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass
