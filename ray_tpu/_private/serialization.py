"""Object serialization.

Analog of the reference's pickle5 + out-of-band-buffer scheme
(reference: python/ray/_private/serialization.py — cloudpickle protocol 5
with zero-copy numpy buffers landing in plasma).  Values are pickled with
cloudpickle protocol 5; large contiguous buffers (numpy arrays, and JAX
arrays via a lazy copyreg hook) are captured out-of-band so they can be
placed in / read from the shared-memory object store without a copy.
"""

from __future__ import annotations

import copyreg
import pickle
import sys
import threading
from dataclasses import dataclass, field
from typing import Any, List, Sequence

import cloudpickle
import numpy as np

# Metadata tags (analog: ray_constants OBJECT_METADATA_TYPE_*)
META_PICKLE = b"py"
META_RAW = b"raw"  # value is raw bytes, stored as-is, zero-copy
META_TASK_ERROR = b"err"
META_ACTOR_HANDLE = b"actor"
# device-tier envelope (core/DEVICE_TIER.md): the HOST-side form of a
# device-resident array — written only when a device object leaves the
# device plane (LRU spill device→shm, or a host-fallback fetch).  inband
# is a msgpack [kind, dtype_str, shape] header; buffers[0] is the raw
# array image.  Refs stay ordinary ObjectRefs: a consumer that finds this
# envelope in shm re-materializes the array without knowing it ever
# lived on a device.
META_DEVICE = b"dev"

_jax_reducer_installed = False

# Contained-ref capture: while serialize() runs, ObjectRef.__reduce__ records
# every ref pickled into the payload here.  The ids ride the control message
# that ships the payload (PUT_OBJECT / TaskSpec.nested_refs / TASK_DONE), so
# the head can pin inner objects for as long as their container is in scope —
# the owner-centralized form of the reference's borrower protocol
# (reference: src/ray/core_worker/reference_count.cc), which exists to close
# the window where the sender releases a shipped ref before the receiver has
# registered its own.
_capture = threading.local()


def _begin_ref_capture() -> list:
    stack = getattr(_capture, "stack", None)
    if stack is None:
        stack = _capture.stack = []
    frame: list = []
    stack.append(frame)
    return frame


def _end_ref_capture(frame: list) -> List[bytes]:
    stack = getattr(_capture, "stack", None)
    if stack and stack[-1] is frame:
        stack.pop()
    # dedup, keep order
    return list(dict.fromkeys(frame))


def record_contained_ref(oid: bytes):
    """Called by ObjectRef.__reduce__ during an active serialize()."""
    stack = getattr(_capture, "stack", None)
    if stack:
        stack[-1].append(oid)


def _maybe_install_jax_reducer():
    """Register a reducer for jax.Array the first time jax shows up.

    Device arrays are pulled to host as numpy (which pickles out-of-band,
    zero-copy) and re-materialized with jnp.asarray on load — which
    initialises a backend in the process that loads them: a driver that
    must stay off the chip has its TPU workers report plain numbers
    (chip_smoke.py) or pins its own jax to the CPU.  Importing jax
    eagerly in every worker would add seconds of startup, so this only
    fires once jax is already in sys.modules.
    """
    global _jax_reducer_installed
    if _jax_reducer_installed or "jax" not in sys.modules:
        return
    import jax
    import jax.numpy as jnp

    def _rebuild(np_value):
        return jnp.asarray(np_value)

    def _reduce_jax_array(arr):
        return (_rebuild, (np.asarray(arr),))

    try:
        from jax._src.array import ArrayImpl

        copyreg.pickle(jax.Array, _reduce_jax_array)
        # concrete ArrayImpl class is what instances actually carry.
        # Imported, NOT discovered via type(jnp.zeros(())): creating an
        # array initializes a backend, and this runs in drivers, heads'
        # clients and pool workers that must never take the chip.
        copyreg.pickle(ArrayImpl, _reduce_jax_array)
    except Exception as e:  # noqa: BLE001
        import warnings

        warnings.warn(
            f"installing the zero-copy jax.Array reducer failed "
            f"({type(e).__name__}: {e}); jax arrays fall back to in-band "
            "pickling",
            stacklevel=2,
        )
    _jax_reducer_installed = True


@dataclass
class SerializedObject:
    """A value split into metadata, in-band pickle bytes, and raw buffers."""

    metadata: bytes
    inband: bytes
    buffers: List[memoryview] = field(default_factory=list)
    # ObjectRef ids pickled inside this value (borrower pinning; not on the
    # data-plane wire — shipped via the control message that moves the value)
    contained: List[bytes] = field(default_factory=list)

    def total_bytes(self) -> int:
        return len(self.inband) + sum(b.nbytes for b in self.buffers)

    def to_wire(self) -> list:
        """msgpack-compatible representation (copies buffers)."""
        return [self.metadata, self.inband, [bytes(b) for b in self.buffers]]

    @classmethod
    def from_wire(cls, wire: Sequence) -> "SerializedObject":
        meta, inband, bufs = wire
        return cls(bytes(meta), bytes(inband), [memoryview(b) for b in bufs])


def serialize(value: Any) -> SerializedObject:
    _maybe_install_jax_reducer()
    if isinstance(value, bytes):
        return SerializedObject(META_RAW, b"", [memoryview(value)])
    buffers: List[pickle.PickleBuffer] = []
    frame = _begin_ref_capture()
    try:
        inband = cloudpickle.dumps(value, protocol=5, buffer_callback=buffers.append)
    finally:
        contained = _end_ref_capture(frame)
    views = []
    for pb in buffers:
        try:
            views.append(pb.raw())
        except BufferError:
            # non-contiguous buffer: force a contiguous copy
            views.append(memoryview(bytes(pb)))
    return SerializedObject(META_PICKLE, inband, views, contained)


def serialize_device_payload(host_view, kind: str, dtype_str: str, shape) -> SerializedObject:
    """Build the META_DEVICE envelope for a device array's host image.

    ``host_view`` is a contiguous byte view of the array (NOT copied here
    — put_serialized streams it into shm directly); ``kind`` records what
    to rebuild on read ("jax" or "np") so a get() after spill is
    bit-and-type-identical to a device-plane get."""
    import msgpack

    header = msgpack.packb([kind, dtype_str, list(shape)], use_bin_type=True)
    return SerializedObject(META_DEVICE, header, [memoryview(host_view).cast("B")])


def deserialize_device_payload(obj: SerializedObject) -> Any:
    """Re-materialize a device array from its META_DEVICE envelope."""
    import msgpack

    kind, dtype_str, shape = msgpack.unpackb(obj.inband, raw=False)
    buf = obj.buffers[0] if obj.buffers else b""
    arr = np.frombuffer(buf, dtype=np.dtype(dtype_str)).reshape(shape)
    if kind == "jax":
        import jax.numpy as jnp

        return jnp.asarray(arr)
    # numpy path: the frombuffer view is read-only over a store view whose
    # pin dies with the SerializedObject — hand back an owning copy
    return np.array(arr)


def deserialize(obj: SerializedObject) -> Any:
    _maybe_install_jax_reducer()
    if obj.metadata == META_RAW:
        return bytes(obj.buffers[0]) if obj.buffers else b""
    if obj.metadata == META_DEVICE:
        return deserialize_device_payload(obj)
    value = pickle.loads(obj.inband, buffers=obj.buffers)
    return value


def dumps(value: Any) -> bytes:
    """Flat single-buffer form, for control-plane payloads."""
    _maybe_install_jax_reducer()
    return cloudpickle.dumps(value, protocol=5)


def loads(data: bytes) -> Any:
    _maybe_install_jax_reducer()
    return pickle.loads(data)
