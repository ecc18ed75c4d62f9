"""Shared small utilities (no jax device state at import time)."""
from __future__ import annotations

import base64
import contextlib
import dataclasses
import json
import os
import tempfile
import zlib
from typing import Any, Iterable

import jax
import numpy as np


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and the directory is left alone.  Otherwise the cache lives at
    the fixed path ``<checkout>/.jax_cache`` (git-ignored): the directory
    is part of what a later process must find again, so it never depends
    on a temp name, a PID or the time.

    The cache key includes the programs' metadata (name stacks, source
    lines).  JAX's default key strips it, so a program that differs from a
    cached one only in its ``jax.named_scope`` phases would load the cached
    executable, and a profile would show that executable's stale names in
    place of the phases ``repro.core.tracing`` marks."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class IntegrityError(RuntimeError):
    """A stored or transmitted artifact failed its checksum.

    Raised at every verification boundary (chunk section, spill batch,
    ckpt block, wire frame, manifest) with a message naming the damaged
    artifact — never a silent wrong result."""


def crc32(data, seed: int = 0) -> int:
    """CRC32 of ``data`` (bytes / buffer / ndarray), as unsigned int."""
    if isinstance(data, np.ndarray):
        data = np.ascontiguousarray(data)
    return zlib.crc32(memoryview(data).cast("B"), seed) & 0xFFFFFFFF


def json_crc(obj: Any) -> int:
    """Canonical CRC32 of a JSON-serializable object (sorted keys)."""
    return crc32(json.dumps(obj, sort_keys=True).encode())


def pack_bools(a) -> str:
    """Bool array -> base64 bitmap string (JSON-friendly; the run-log
    representation of a per-op active mask)."""
    a = np.asarray(a, bool)
    return base64.b64encode(np.packbits(a.reshape(-1)).tobytes()).decode(
        "ascii")


def unpack_bools(s: str, shape) -> np.ndarray:
    """Inverse of :func:`pack_bools` for a known shape."""
    raw = np.frombuffer(base64.b64decode(s), np.uint8)
    n = int(np.prod(shape))
    return np.unpackbits(raw, count=n).reshape(shape).astype(bool)


def atomic_write_json(path: str, obj: Any) -> None:
    """Write JSON via tmp-file + rename so a crash mid-write never leaves a
    truncated file behind (the blockstore/chunkstore manifest commit point)."""
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(os.path.abspath(path)))
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.remove(tmp)
        raise


def register_static_dataclass(cls, data_fields: Iterable[str], static_fields: Iterable[str]):
    """Register a dataclass as a pytree with explicit data/static split."""
    jax.tree_util.register_dataclass(
        cls, data_fields=list(data_fields), meta_fields=list(static_fields)
    )
    return cls


def tree_bytes(tree: Any) -> int:
    """Total bytes of all array leaves in a pytree."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        if hasattr(leaf, "nbytes"):
            total += leaf.nbytes
        elif hasattr(leaf, "dtype") and hasattr(leaf, "shape"):
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def tree_params(tree: Any) -> int:
    """Total number of elements of all array leaves in a pytree."""
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree)
               if hasattr(l, "shape"))


def token_ctx(lock):
    """Context manager over an optional shared compute token: the lock
    itself when given, a no-op otherwise.

    The parallel dist_ooc executor hands one lock to every CPU-bound burst
    in its worker pipelines (combine, dispatch, wire decode, chunk decode
    — DESIGN.md §8); holding it for a whole work item lets W threads take
    orderly turns at the host CPU instead of convoying on the GIL at every
    small numpy call, while disk waits and queue handoffs stay outside the
    token and genuinely overlap.  Sequential pipelines pass None and pay
    nothing."""
    return lock if lock is not None else contextlib.nullcontext()


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b


@dataclasses.dataclass
class HardwareSpec:
    """Roofline constants for the target chip (TPU v5e by default)."""
    name: str = "tpu_v5e"
    peak_flops: float = 197e12          # bf16 FLOP/s per chip
    hbm_bw: float = 819e9               # bytes/s per chip
    ici_bw: float = 50e9                # bytes/s per ICI link
    ici_links: int = 4                  # usable links per chip (2D torus slice)
    hbm_bytes: int = 16 * 2**30         # HBM capacity
    vmem_bytes: int = 128 * 2**20       # VMEM capacity


V5E = HardwareSpec()
