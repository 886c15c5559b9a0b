"""Serialization: cloudpickle envelope with out-of-band zero-copy buffers.

TPU-native analog of the reference's serialization layer
(python/ray/_private/serialization.py: msgpack envelope + pickle5 out-of-band buffers;
zero-copy numpy reads from plasma). Design:

- ``serialize(obj) -> (meta: bytes, buffers: list[memoryview/bytes])`` using pickle5
  protocol with buffer_callback, so large numpy / jax host arrays are captured as
  out-of-band buffers and can be written into (and later mapped zero-copy out of) the
  shared-memory object store.
- jax.Array device values are pulled to host (np.asarray) at put() time — device
  residency across process boundaries is handled by the L4 channel layer, not the
  object store (matching the reference, where GPU tensors bypass plasma via
  NCCL/RDT: python/ray/experimental/rdt/).
- Exceptions are wrapped so they re-raise at ``get`` (reference:
  RayTaskError in python/ray/exceptions.py).
"""

from __future__ import annotations

import pickle
import sys
from typing import Any, Iterable

import cloudpickle


def _jax_array_types():
    # Never IMPORT jax here: a value can only be a jax.Array if jax is already
    # loaded in this process, and importing jax in a fresh worker is multi-
    # second.
    jax = sys.modules.get("jax")
    if jax is None:
        return ()
    try:
        return (jax.Array,)
    except AttributeError:  # partially-imported jax
        return ()


def _to_host(obj: Any) -> Any:
    """Convert device arrays to host numpy for cross-process transport."""
    import numpy as np

    if _jax_array_types() and isinstance(obj, _jax_array_types()):
        return np.asarray(obj)
    return obj


def serialize(obj: Any) -> tuple[bytes, list]:
    """Serialize to (metadata, out-of-band buffers)."""
    buffers: list[pickle.PickleBuffer] = []
    obj = _to_host(obj)
    meta = cloudpickle.dumps(obj, protocol=5, buffer_callback=buffers.append)
    return meta, [b.raw() for b in buffers]


def deserialize(meta: bytes, buffers: Iterable) -> Any:
    return pickle.loads(meta, buffers=list(buffers))


def serialize_to_bytes(obj: Any) -> bytes:
    """Single-blob form: 4-byte buffer count + lengths header + concatenated payloads."""
    _, parts = serialize_parts(obj)
    return b"".join(bytes(p) if not isinstance(p, (bytes, bytearray)) else p for p in parts)


def serialize_parts(obj: Any) -> tuple[int, list]:
    """Like serialize_to_bytes but WITHOUT the final concatenation copy:
    returns (total_size, parts) where writing the parts back-to-back produces
    exactly the single-blob format. Lets the shm store scatter-copy large
    arrays straight into the mapped arena (one memcpy total instead of two)."""
    import struct

    meta, bufs = serialize(obj)
    mvs = [memoryview(b).cast("B") for b in bufs]
    header = struct.pack(">I", len(mvs)) + b"".join(
        struct.pack(">Q", n) for n in [len(meta)] + [m.nbytes for m in mvs]
    )
    parts = [header, meta, *mvs]
    return len(header) + len(meta) + sum(m.nbytes for m in mvs), parts


def deserialize_from_bytes(data) -> Any:
    import struct

    mv = memoryview(data)
    (nbuf,) = struct.unpack_from(">I", mv, 0)
    off = 4
    lengths = []
    for _ in range(nbuf + 1):
        (ln,) = struct.unpack_from(">Q", mv, off)
        lengths.append(ln)
        off += 8
    meta = bytes(mv[off : off + lengths[0]])
    off += lengths[0]
    bufs = []
    for ln in lengths[1:]:
        bufs.append(mv[off : off + ln])  # zero-copy view into the source buffer
        off += ln
    return deserialize(meta, bufs)
