"""ObjectRef + ObjectRefGenerator: a first-class future naming an object owned by some worker.

Parity: ray.ObjectRef (python/ray/includes/object_ref.pxi). The ref carries
its owner's address so any holder can locate the value without a directory
lookup — the ownership model of the reference (src/ray/core_worker/
reference_counter.h:44). Refs are pickleable; deserializing one in another
process registers a borrow with the owner (round-1: release on driver GC).
"""

from __future__ import annotations

from typing import Optional

from ray_tpu.utils.ids import ObjectID


class ObjectRef:
    __slots__ = ("id", "owner_address", "_weak")

    def __init__(self, object_id: ObjectID, owner_address: str = "", weak: bool = False):
        self.id = object_id
        self.owner_address = owner_address
        # weak refs don't participate in refcounting (internal bookkeeping)
        self._weak = weak
        if not weak:
            _get_tracker().add_local_ref(self)

    def hex(self) -> str:
        return self.id.hex()

    def binary(self) -> bytes:
        return self.id.binary()

    def task_id(self):
        return self.id.task_id()

    def job_id(self):
        return self.id.job_id()

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other.id == self.id

    def __hash__(self):
        return hash(self.id)

    def __repr__(self):
        return f"ObjectRef({self.id.hex()})"

    def __reduce__(self):
        # Crossing a process boundary: create an in-flight pin at the owner
        # keyed by a fresh token; the deserializer's add_borrow consumes the
        # token so the pin transfers to the borrower (and is released when
        # the borrower's last local ref is GC'd).
        import uuid

        token = uuid.uuid4().hex
        _get_tracker().on_serialize(self, token)
        return (_deserialize_ref, (self.id, self.owner_address, token))

    def __del__(self):
        if not self._weak:
            try:
                _get_tracker().remove_local_ref(self)
            except Exception:
                pass

    def future(self):
        """Return a concurrent.futures.Future resolving to the value."""
        import concurrent.futures
        import threading

        from ray_tpu.core import api

        fut: concurrent.futures.Future = concurrent.futures.Future()

        def wait_thread():
            try:
                fut.set_result(api.get(self))
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

        threading.Thread(target=wait_thread, daemon=True).start()
        return fut


def _deserialize_ref(
    object_id: ObjectID, owner_address: str, token: Optional[str] = None
) -> ObjectRef:
    # weak=False: the borrow must be released when the local ref is GC'd,
    # so the ref participates in local refcounting like any other.
    ref = ObjectRef(object_id, owner_address, weak=False)
    _get_tracker().on_deserialize(ref, token)
    return ref


class _NullTracker:
    def add_local_ref(self, ref):
        pass

    def remove_local_ref(self, ref):
        pass

    def on_serialize(self, ref, token):
        pass

    def on_deserialize(self, ref, token):
        pass


_null_tracker = _NullTracker()


def _get_tracker():
    """The current process's reference tracker (CoreWorker), if connected."""
    from ray_tpu.core import worker as worker_mod

    w = worker_mod.global_worker_or_none()
    if w is None:
        return _null_tracker
    return w.reference_tracker


class ObjectRefGenerator:
    """Iterator over a streaming task's yielded values (parity: the
    reference's streaming generators, num_returns="streaming" — dynamic
    return objects arrive as the executor produces them, long before the
    task finishes).

    Yields ObjectRefs in yield order; raises the task's error (if it
    failed) when iteration reaches it. Owner-process only (the consumer
    is the task's submitter)."""

    def __init__(self, task_id, worker):
        self._task_id = task_id
        self._worker = worker
        self._i = 0
        # set by the worker when an item of this task, or its end, lands
        self._landed = worker.stream_event(task_id)

    def __del__(self):
        try:
            self._worker.drop_stream_event(self._task_id)
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass

    def __iter__(self):
        return self

    def __next__(self) -> "ObjectRef":
        return self.next_ref()

    def next_ref(self, timeout_s=None) -> "ObjectRef":
        import time as _time

        from ray_tpu.core import object_store as os_mod
        from ray_tpu.core.exceptions import GetTimeoutError, ObjectLostError
        from ray_tpu.utils.config import config
        from ray_tpu.utils.ids import ObjectID

        w = self._worker
        oid = ObjectID.from_task(self._task_id, self._i)
        done_oid = w._stream_done_oid(self._task_id)
        deadline = None if timeout_s is None else _time.monotonic() + timeout_s
        lost_deadline = None
        err_deadline = None
        while True:
            # what lands from here on sets the event again: nothing that
            # arrives between the checks below and the wait is slept through
            self._landed.clear()
            # Consult the final COUNT before yielding: a retried task can
            # leave stale items from the failed attempt at indices past
            # the final count — those must not be yielded. An Exception
            # marker, by contrast, raises only after the present prefix of
            # items has been consumed (they were validly produced).
            marker = w.memory_store.try_get(done_oid)
            has_marker = not os_mod.is_missing(marker)
            is_err = has_marker and isinstance(marker, Exception)
            if has_marker and not is_err and self._i >= int(marker):
                raise StopIteration
            if w.memory_store.contains(oid):
                self._i += 1
                return ObjectRef(oid, w.address)
            if is_err:
                # the error reply rides a different connection than the
                # in-order item pushes and can overtake them: give items
                # yielded before the failure a short grace to land
                if err_deadline is None:
                    err_deadline = (
                        _time.monotonic() + config.stream_error_grace_s
                    )
                elif _time.monotonic() > err_deadline:
                    raise marker
            if has_marker and not is_err:
                # count says item i exists but its push is still in
                # flight on another connection: give it a bounded grace —
                # the push can be silently lost (executor->owner link died
                # after the count reply landed), and an unbounded wait
                # would spin forever.
                if lost_deadline is None:
                    lost_deadline = (
                        _time.monotonic() + config.stream_item_grace_s
                    )
                elif _time.monotonic() > lost_deadline:
                    raise ObjectLostError(
                        f"streamed item {self._i} of task "
                        f"{self._task_id.hex()} was yielded but its value "
                        "never arrived (push lost)"
                    )
            if deadline is not None and _time.monotonic() > deadline:
                raise GetTimeoutError(
                    f"streamed item {self._i} of task "
                    f"{self._task_id.hex()} not available"
                )
            # woken by this stream's own arrivals; the timeout is for the
            # deadlines above, which nothing signals
            self._landed.wait(0.05)

    def ready_refs(self) -> "list[ObjectRef]":
        """The items that have already arrived, in yield order, without
        waiting: what a consumer that fell behind can take in one go
        after ``next_ref`` gave it one. Never past the final count (a
        retried task's stale items, see ``next_ref``); an error marker
        is left for ``next_ref`` to raise."""
        from ray_tpu.core import object_store as os_mod
        from ray_tpu.utils.ids import ObjectID

        w = self._worker
        marker = w.memory_store.try_get(w._stream_done_oid(self._task_id))
        limit = None
        if not os_mod.is_missing(marker) and not isinstance(marker, Exception):
            limit = int(marker)
        refs = []
        while limit is None or self._i < limit:
            oid = ObjectID.from_task(self._task_id, self._i)
            if not w.memory_store.contains(oid):
                break
            self._i += 1
            refs.append(ObjectRef(oid, w.address))
        return refs

    def completed(self) -> bool:
        from ray_tpu.core import object_store as os_mod

        return not os_mod.is_missing(
            self._worker.memory_store.try_get(
                self._worker._stream_done_oid(self._task_id)
            )
        )
