"""Reader decorators (reference: python/paddle/reader/decorator.py), the
PyReader program-integrated reader (reference python/paddle/fluid/
reader.py:46 -> operators/reader/create_py_reader_op.cc +
LoDTensorBlockingQueue), and the host->device prefetcher that replaces the
reference's double-buffered reader (operators/reader/buffered_reader.cc).

Pipeline shape on TPU: reader threads (python generator, or the native C++
queue behind QueueDataset) produce numpy batches -> DeviceFeeder's
transfer thread issues jax.device_put ahead of consumption (the H2D copy
runs on its own stream) -> the train loop pops device-resident batches, so
feed transfer overlaps the previous step's compute exactly like the
reference's double-buffered reader overlaps cudaMemcpyAsync with kernels.
"""

from __future__ import annotations

import itertools
import random as _random
import time
from queue import Queue
from threading import Thread

from paddle_tpu.observability import step_record as _obs_steps

__all__ = [
    "batch", "shuffle", "buffered", "cache", "chain", "compose", "firstn",
    "map_readers", "xmap_readers", "PyReader", "DataLoader",
    "DeviceFeeder",
]


def batch(reader, batch_size, drop_last=False):
    def batch_reader():
        b = []
        for item in reader():
            b.append(item)
            if len(b) == batch_size:
                yield b
                b = []
        if b and not drop_last:
            yield b

    return batch_reader


def shuffle(reader, buf_size):
    def shuffle_reader():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) >= buf_size:
                _random.shuffle(buf)
                yield from buf
                buf = []
        _random.shuffle(buf)
        yield from buf

    return shuffle_reader


def buffered(reader, size):
    end = object()

    def buffered_reader():
        q = Queue(maxsize=size)

        def worker():
            for item in reader():
                q.put(item)
            q.put(end)

        t = Thread(target=worker, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is end:
                return
            yield item

    return buffered_reader


def cache(reader):
    data = []
    filled = [False]

    def cache_reader():
        if not filled[0]:
            for item in reader():
                data.append(item)
                yield item
            filled[0] = True
        else:
            yield from data

    return cache_reader


def chain(*readers):
    def chain_reader():
        for r in readers:
            yield from r()

    return chain_reader


def compose(*readers, check_alignment=True):
    def compose_reader():
        its = [r() for r in readers]
        for items in zip(*its):
            out = []
            for it in items:
                if isinstance(it, tuple):
                    out.extend(it)
                else:
                    out.append(it)
            yield tuple(out)

    return compose_reader


def firstn(reader, n):
    def firstn_reader():
        yield from itertools.islice(reader(), n)

    return firstn_reader


def map_readers(func, *readers):
    def reader():
        its = [r() for r in readers]
        for items in zip(*its):
            yield func(*items)

    return reader


def xmap_readers(mapper, reader, process_num, buffer_size, order=False):
    """Multithreaded map (reference decorator.py xmap_readers)."""
    end = object()

    def xreader():
        in_q: Queue = Queue(buffer_size)
        out_q: Queue = Queue(buffer_size)

        def feeder():
            for i, item in enumerate(reader()):
                in_q.put((i, item))
            for _ in range(process_num):
                in_q.put(end)

        def worker():
            while True:
                item = in_q.get()
                if item is end:
                    out_q.put(end)
                    return
                i, x = item
                out_q.put((i, mapper(x)))

        Thread(target=feeder, daemon=True).start()
        for _ in range(process_num):
            Thread(target=worker, daemon=True).start()
        finished = 0
        pending = {}
        next_i = 0
        while finished < process_num:
            item = out_q.get()
            if item is end:
                finished += 1
                continue
            if not order:
                yield item[1]
            else:
                pending[item[0]] = item[1]
                while next_i in pending:
                    yield pending.pop(next_i)
                    next_i += 1
        if order:
            for i in sorted(pending):
                yield pending[i]

    return xreader


class DeviceFeeder:
    """Async host->device prefetcher (reference buffered_reader.cc).

    Two daemon threads double-buffer the feed path:
      * producer: drains ``batch_iter`` (python generator or the native
        C++ BlockingQueue consumer) into a bounded host queue;
      * transfer: pops a host batch, issues ``jax.device_put`` (async —
        the copy engine runs while the device computes), and parks up to
        ``device_prefetch`` device-resident batches.

    Iterating yields feed dicts whose values are already on device, so
    ``Executor.run`` skips the host round-trip entirely (compiler.py
    feeds jax.Array values straight through)."""

    _END = object()

    def __init__(self, batch_iter, capacity=8, device_prefetch=2,
                 to_device=True):
        self._host_q: Queue = Queue(maxsize=max(2, capacity))
        self._dev_q: Queue = Queue(maxsize=max(1, device_prefetch))
        self._err = []
        self._stopped = False
        self._to_device = to_device

        def producer():
            try:
                for item in batch_iter:
                    if self._stopped:
                        return
                    self._host_q.put(item)
            except BaseException as e:  # surfaced on the consumer side
                self._err.append(e)
            finally:
                self._host_q.put(DeviceFeeder._END)

        def transfer():
            import jax

            try:
                while True:
                    t_get = time.perf_counter_ns()
                    item = self._host_q.get()
                    if item is DeviceFeeder._END or self._stopped:
                        break
                    # one `put` record a batch (observability/
                    # step_record.py): when this thread issued the
                    # copies, against when the consumer was in exe.run
                    rec = _obs_steps.Record("put", bytes=sum(
                        getattr(v, "nbytes", 0) for v in item.values()))
                    rec.stamp("start", phase="feeder.put")
                    try:
                        if self._to_device:
                            item = {k: jax.device_put(v)
                                    for k, v in item.items()}
                        rec.stamp("end", phase=None)
                        self._dev_q.put(item)
                        f = rec.fields
                        f["host_wait"] = f["start"] - t_get
                        f["dev_wait"] = time.perf_counter_ns() - f["end"]
                    finally:
                        rec.done()
            except BaseException as e:
                self._err.append(e)
            finally:
                self._dev_q.put(DeviceFeeder._END)

        self._threads = [Thread(target=producer, daemon=True),
                         Thread(target=transfer, daemon=True)]
        for t in self._threads:
            t.start()

    def __iter__(self):
        return self

    def __next__(self):
        rec = _obs_steps.Record("next")
        rec.stamp("start")
        item = self._dev_q.get()
        rec.done()
        if item is DeviceFeeder._END:
            # stay drained: re-park the sentinel so another next() raises
            # again instead of blocking on the empty queue forever
            self._dev_q.put(DeviceFeeder._END)
            if self._err:
                raise self._err[0]
            raise StopIteration
        return item

    def stop(self):
        self._stopped = True
        # unblock the threads if they are parked on full/empty queues,
        # then re-park sentinels: the transfer thread may loop back to
        # host_q.get() after its put unblocks, and consumers may call
        # next() again — both must see END, not block forever
        for q in (self._host_q, self._dev_q):
            try:
                while True:
                    q.get_nowait()
            except Exception:
                pass
            try:
                q.put_nowait(DeviceFeeder._END)
            except Exception:
                pass


class PyReader:
    """Reader bound to feed vars (reference python/paddle/fluid/
    reader.py:46).

    Iterable mode: ``for feed in reader: exe.run(feed=feed, ...)`` — each
    yielded dict holds device-resident arrays prefetched by DeviceFeeder.

    Non-iterable (program-integrated) mode, built by ``layers.py_reader``:
    the program carries a host-only ``read`` op; ``reader.start()`` spins
    the prefetcher, each ``exe.run()`` (no feed) pops the next batch, and
    exhaustion raises ``fluid.core.EOFException`` — then ``reset()`` and
    ``start()`` again, exactly the reference loop."""

    def __init__(self, feed_list=None, capacity=64, iterable=True,
                 return_list=False, use_prefetch=True):
        self.feed_list = feed_list or []
        self.capacity = capacity
        self.iterable = iterable
        self.return_list = return_list
        self._use_prefetch = use_prefetch
        self._generator = None
        self._batched = False
        self._feeder = None

    def decorate_sample_list_generator(self, generator, places=None):
        self._generator = generator
        self._batched = True

    # reference name for the same thing (paddle.batch-ed reader)
    decorate_paddle_reader = decorate_sample_list_generator

    def decorate_batch_generator(self, generator, places=None):
        self._generator = generator
        self._batched = False

    def _feed_dicts(self):
        import numpy as np

        names = [v.name for v in self.feed_list]
        for sample in self._generator():
            if self._batched:
                cols = list(zip(*sample))
                arrays = [np.asarray(c) for c in cols]
            else:
                arrays = [np.asarray(c) for c in sample]
            yield dict(zip(names, arrays))

    def __iter__(self):
        if self._generator is None:
            return iter(())
        if not self._use_prefetch:
            return self._feed_dicts()
        return DeviceFeeder(self._feed_dicts(), capacity=self.capacity)

    # -- non-iterable (program-integrated) mode -----------------------------
    def start(self):
        if self._generator is None:
            raise RuntimeError("decorate a generator before start()")
        if self._use_prefetch:
            self._feeder = DeviceFeeder(self._feed_dicts(),
                                        capacity=self.capacity)
        else:  # use_double_buffer=False: no background threads
            self._feeder = iter(self._feed_dicts())

    def reset(self):
        if isinstance(self._feeder, DeviceFeeder):
            self._feeder.stop()
        self._feeder = None

    def _next_batch(self):
        from paddle_tpu.core import EOFException

        if self._feeder is None:
            raise RuntimeError(
                "py_reader not started — call reader.start() first")
        try:
            return next(self._feeder)
        except StopIteration:
            self._feeder = None
            raise EOFException("py_reader drained") from None


# program-integrated readers by name (reference: ReaderHolder variables in
# the scope; here the queue lives host-side so a name registry suffices)
_PY_READERS: dict = {}


def register_py_reader(name, reader):
    _PY_READERS[name] = reader


def get_py_reader(name):
    return _PY_READERS[name]


def _read_ops(program):
    """Cached list of 'read' ops in the global block (recomputed when the
    op count changes — keeps the common no-reader hot path O(1))."""
    block = program.global_block()
    cached = getattr(program, "_read_ops_cache", None)
    if cached is not None and cached[0] == len(block.ops):
        return cached[1]
    ops = [op for op in block.ops if op.type == "read"]
    program._read_ops_cache = (len(block.ops), ops)
    return ops


def augment_feed_from_readers(program, feed):
    """For each 'read' op whose outputs the caller did not feed, pop the
    next prefetched batch from its reader into `feed`.  Used by the
    compiled path, where the host-only read op is skipped in the trace and
    its outputs arrive as ordinary (device-resident) feeds.  Raises
    fluid.core.EOFException when a reader is drained."""
    for op in _read_ops(program):
        names = op.outputs.get("Out", [])
        fed = [n for n in names if n in feed]
        if names and len(fed) == len(names):
            continue
        if fed:
            raise ValueError(
                f"read op outputs partially fed ({fed}): feed all of "
                f"{names} to override the reader, or none to consume a "
                "batch")
        reader = _PY_READERS.get(op.attrs["reader_name"])
        if reader is None:
            raise RuntimeError(
                f"read op references unknown reader "
                f"'{op.attrs['reader_name']}'")
        feed.update(reader._next_batch())
    return feed


class DataLoader:
    """Modern facade (reference 1.5-era fluid.io.DataLoader precursor)."""

    @staticmethod
    def from_generator(feed_list=None, capacity=64, iterable=True,
                       return_list=False, use_double_buffer=True):
        return PyReader(feed_list, capacity, iterable, return_list,
                        use_prefetch=use_double_buffer)
