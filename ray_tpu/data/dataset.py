"""Dataset: lazy, streaming, distributed (reference: ray
python/ray/data/dataset.py — 5.2k LoC; transforms map/map_batches/flat_map/
filter/repartition/random_shuffle/sort/zip/union/limit/groupby, consumption
iter_batches/iter_rows/take/count, splits streaming_split:1223/split,
writes write_parquet/csv/json/numpy).

TPU-native addition: iter_jax_batches yields device-put (optionally sharded)
jax arrays — the input pipeline ends on-device (SURVEY §7 "zero-copy
plasma→device" path).
"""

from __future__ import annotations

import builtins
from typing import Any, Callable, Dict, Iterator, List, Optional, Union

import numpy as np

from ray_tpu.data._internal.executor import (
    DEFAULT_MAX_IN_FLIGHT,
    execute_refs,
    execute_streaming,
)
from ray_tpu.data._internal.plan import Operator, Plan
from ray_tpu.data.block import Block, BlockAccessor


def _shard_host_batch(v, sharding, _jax=None):
    """One host numpy column → a global jax.Array under `sharding`.

    Fully-addressable shardings (single-process mesh): slice the host
    batch per device and device_put each slice to the device that owns it
    (`make_array_from_single_device_arrays`) — no device ever holds the
    full batch. Multi-process shardings: this process's rows are its shard
    of the global batch (`make_array_from_process_local_data`). Anything
    that isn't a jax Sharding (a bare device) keeps plain device_put.

    `_jax`: the already-imported jax module — iter_jax_batches passes it so
    per-batch, per-column calls skip the import-machinery lookup.
    """
    jax = _jax
    if jax is None:
        import jax

    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.device_put(v, sharding)
    if not sharding.is_fully_addressable:
        return jax.make_array_from_process_local_data(sharding, v)
    global_shape = v.shape
    idx_map = sharding.addressable_devices_indices_map(global_shape)
    shards = [jax.device_put(v[idx], dev) for dev, idx in idx_map.items()]
    return jax.make_array_from_single_device_arrays(
        global_shape, sharding, shards)


_FEED_DONE = object()


def _prefetch_device_feed(src: Iterator, to_device: Callable, depth: int,
                          stats: Optional[Dict] = None) -> Iterator:
    """Double-buffered device feed for iter_jax_batches.

    A daemon producer thread pulls host batches from ``src`` and runs
    ``to_device`` (host assembly + device_put issue) up to ``depth``
    batches ahead of the consumer; the queue bound IS the prefetch depth,
    so device memory holds at most depth+1 in-flight batches. Producer
    exceptions re-raise at the consumer's next pull; abandoning the
    iterator (generator close / early break) stops the producer and joins
    it — no leaked non-daemon work.

    ``stats`` gets produce_s (producer busy seconds), wait_s (consumer
    seconds blocked on an empty queue), batches, and overlap_frac =
    1 - wait_s/produce_s clipped to [0, 1]: the fraction of input-pipeline
    time hidden behind the consumer's compute.
    """
    import queue as _queue
    import threading
    import time as _time

    q: "_queue.Queue" = _queue.Queue(maxsize=max(1, depth))  # bound = depth
    stop = threading.Event()
    acc = {"produce_s": 0.0, "wait_s": 0.0, "batches": 0}

    def _produce():
        try:
            it = iter(src)
            while True:
                # produce_s covers the WHOLE input pipeline stage: the
                # upstream host-batch pull (block execution / arena reads)
                # plus assembly + device_put issue — that is the work the
                # overlap hides behind the consumer's compute
                t0 = _time.perf_counter()
                batch = next(it, _FEED_DONE)
                if batch is _FEED_DONE:
                    break
                out = to_device(batch)
                acc["produce_s"] += _time.perf_counter() - t0
                while not stop.is_set():
                    try:
                        q.put(out, timeout=0.1)
                        break
                    except _queue.Full:
                        continue
                if stop.is_set():
                    return
            q.put(_FEED_DONE)
        except BaseException as e:  # noqa: BLE001 — re-raised at consumer
            if not stop.is_set():
                q.put(e)

    t = threading.Thread(target=_produce, name="rt-data-device-feed",
                         daemon=True)
    t.start()
    from ray_tpu._private.device_profiler import observe_phase

    try:
        while True:
            t0 = _time.perf_counter()
            item = q.get()
            wait = _time.perf_counter() - t0
            acc["wait_s"] += wait
            # feed the cluster-wide device-plane histogram (ISSUE 15):
            # consumer seconds blocked on the feed ARE the train step's
            # input_wait phase, visible next to device_execute in
            # ray_tpu_step_phase_seconds without any trainer plumbing
            observe_phase("input_wait", wait)
            if item is _FEED_DONE:
                break
            if isinstance(item, BaseException):
                raise item
            acc["batches"] += 1
            yield item
    finally:
        stop.set()
        while True:  # unblock a producer parked on q.put
            try:
                q.get_nowait()
            except _queue.Empty:
                break
        t.join(timeout=10)
        if stats is not None:
            stats.update(acc)
            busy = acc["produce_s"]
            stats["overlap_frac"] = (
                max(0.0, min(1.0, 1.0 - acc["wait_s"] / busy))
                if busy > 0 else 0.0)


class Dataset:
    def __init__(self, plan: Plan):
        self._plan = plan
        # ExecutionStats of the most recent consumption of THIS dataset
        # instance (rendered by .stats()).
        self._last_stats = None

    # -- transforms (lazy) ---------------------------------------------------

    def map(self, fn: Callable[[dict], dict], **_kw) -> "Dataset":
        return Dataset(self._plan.with_operator(Operator("map_rows", fn)))

    def map_batches(self, fn: Callable, *, batch_size: Optional[int] = None,
                    batch_format: str = "numpy", fn_args=None, fn_kwargs=None,
                    fn_constructor_args=None, fn_constructor_kwargs=None,
                    concurrency=None, **_kw) -> "Dataset":
        options: Dict[str, Any] = {"batch_size": batch_size,
                                   "batch_format": batch_format}
        if concurrency is not None:
            # callable classes with explicit concurrency run on an
            # autoscaling ACTOR POOL (reference:
            # actor_pool_map_operator.py + execution/autoscaler/
            # default_autoscaler.py): int = fixed size, (min, max) =
            # autoscale between bounds on queue depth
            options["concurrency"] = (
                tuple(concurrency) if isinstance(concurrency, (tuple, list))
                else (int(concurrency), int(concurrency)))
        if isinstance(fn, type):
            # callable class (reference: actor-pool map — one instance per
            # worker process per stage, constructed lazily in the worker);
            # fn_args/fn_kwargs go to __call__, ctor args to __init__
            import uuid as _uuid

            options.update({
                "is_class": True,
                "instance_key": _uuid.uuid4().hex,
                "ctor_args": tuple(fn_constructor_args or ()),
                "ctor_kwargs": dict(fn_constructor_kwargs or {}),
                "call_args": tuple(fn_args or ()),
                "call_kwargs": dict(fn_kwargs or {}),
            })
        elif fn_args or fn_kwargs:
            import functools

            fn = functools.partial(fn, *(fn_args or ()), **(fn_kwargs or {}))
        return Dataset(self._plan.with_operator(Operator(
            "map_batches", fn, options)))

    def flat_map(self, fn: Callable[[dict], List[dict]], **_kw) -> "Dataset":
        return Dataset(self._plan.with_operator(Operator("flat_map", fn)))

    def filter(self, fn: Callable[[dict], bool], **_kw) -> "Dataset":
        return Dataset(self._plan.with_operator(Operator("filter", fn)))

    def limit(self, n: int) -> "Dataset":
        return Dataset(self._plan.with_operator(
            Operator("limit", None, {"n": n})))

    def repartition(self, num_blocks: int, **_kw) -> "Dataset":
        return Dataset(self._plan.with_operator(
            Operator("repartition", None, {"num_blocks": num_blocks})))

    def random_shuffle(self, *, seed: Optional[int] = None, **_kw) -> "Dataset":
        return Dataset(self._plan.with_operator(
            Operator("random_shuffle", None, {"seed": seed})))

    def sort(self, key: Union[str, List[str]],
             descending: bool = False) -> "Dataset":
        return Dataset(self._plan.with_operator(
            Operator("sort", None, {"key": key, "descending": descending})))

    def union(self, *others: "Dataset") -> "Dataset":
        return Dataset(self._plan.with_operator(Operator(
            "union", None, {"other_plans": [o._plan for o in others]})))

    def zip(self, other: "Dataset") -> "Dataset":
        return Dataset(self._plan.with_operator(Operator(
            "zip", None, {"other_plan": other._plan})))

    def add_column(self, name: str, fn: Callable) -> "Dataset":
        def add(batch: Dict[str, np.ndarray]):
            batch[name] = fn(batch)
            return batch

        return self.map_batches(add)

    def drop_columns(self, cols: List[str]) -> "Dataset":
        def drop(batch: Dict[str, np.ndarray]):
            return {k: v for k, v in batch.items() if k not in cols}

        return self.map_batches(drop)

    def select_columns(self, cols: List[str]) -> "Dataset":
        def select(batch: Dict[str, np.ndarray]):
            return {k: batch[k] for k in cols}

        return self.map_batches(select)

    def rename_columns(self, mapping: Dict[str, str]) -> "Dataset":
        def rename(batch: Dict[str, np.ndarray]):
            return {mapping.get(k, k): v for k, v in batch.items()}

        return self.map_batches(rename)

    def groupby(self, key: str) -> "GroupedData":
        from ray_tpu.data.grouped_data import GroupedData

        return GroupedData(self, key)

    def random_sample(self, fraction: float,
                      *, seed: Optional[int] = None) -> "Dataset":
        rng_seed = seed

        def sample(batch: Dict[str, np.ndarray]):
            n = len(next(iter(batch.values()))) if batch else 0
            rng = np.random.default_rng(rng_seed)
            mask = rng.random(n) < fraction
            return {k: v[mask] for k, v in batch.items()}

        return self.map_batches(sample)

    # -- execution -----------------------------------------------------------

    # -- breadth API (reference: data/dataset.py take_batch/copy/
    #    input_files/size_bytes/randomize_block_order/split_proportionately/
    #    aggregate/to_*_refs/to_torch/to_dask/write_images/write_mongo) ----

    def take_batch(self, batch_size: int = 20,
                   batch_format: str = "numpy"):
        """First `batch_size` rows as ONE batch (reference: take_batch)."""
        for batch in self.limit(batch_size).iter_batches(
                batch_size=batch_size, batch_format=batch_format):
            return batch
        return {}

    def copy(self) -> "Dataset":
        """Dataset with an independent plan — transforms applied to the
        copy never affect the original (reference: copy)."""
        return Dataset(self._plan.copy())

    def input_files(self) -> List[str]:
        """Source files of a file-based read ([] otherwise)."""
        return list(self._plan.input_files)

    def size_bytes(self) -> int:
        """Total block bytes after execution (reference: size_bytes)."""
        return builtins.sum(
            BlockAccessor.for_block(b).size_bytes()
            for b in self.iter_blocks())

    def randomize_block_order(self, *, seed: Optional[int] = None
                              ) -> "Dataset":
        """Shuffle BLOCK order without moving rows — cheap decorrelation
        (reference: randomize_block_order). Executes the upstream plan to
        block refs (blocks stay in the object store, never on the driver);
        the result reads from the reordered refs."""
        import ray_tpu

        refs = list(self.iter_internal_block_refs())
        rng = np.random.default_rng(seed)
        refs = [refs[i] for i in rng.permutation(len(refs))]
        return Dataset(Plan([(lambda r=r: [ray_tpu.get(r)]) for r in refs],
                            []))

    def split_proportionately(self, proportions: List[float]
                              ) -> List["MaterializedDataset"]:
        """Split by fractions; the remainder is a final extra split
        (reference: split_proportionately)."""
        if not proportions or any(p <= 0 for p in proportions):
            raise ValueError("proportions must be positive")
        if builtins.sum(proportions) >= 1.0:
            raise ValueError("proportions must sum to < 1 (the remainder "
                             "becomes the last split)")
        n = self.count()
        indices, acc = [], 0.0
        for p in proportions:
            acc += p
            indices.append(int(n * acc))
        return self.split_at_indices(indices)

    def aggregate(self, *aggs) -> Dict[str, Any]:
        """Whole-dataset aggregation -> {agg_name: value} (reference:
        aggregate; AggregateFns from ray_tpu.data.grouped_data)."""
        accs = [a.init() for a in aggs]
        for block in self.iter_blocks():
            batch = BlockAccessor.for_block(block).to_numpy_batch()
            for i, a in enumerate(aggs):
                on = getattr(a, "_on", None)
                col = (batch[on] if on is not None
                       else next(iter(batch.values()), np.empty(0)))
                accs[i] = a.accumulate(accs[i], col)
        return {a.name: a.finalize(acc) for a, acc in zip(aggs, accs)}

    def to_arrow_refs(self) -> List[Any]:
        """One ObjectRef per block; blocks ARE arrow tables here, so this
        is the zero-conversion path (reference: to_arrow_refs)."""
        return list(self.iter_internal_block_refs())

    def to_numpy_refs(self) -> List[Any]:
        """One ObjectRef per block of {col: ndarray} (reference:
        to_numpy_refs); conversion runs as cluster tasks."""
        return [_block_converter("numpy").remote(r)
                for r in self.iter_internal_block_refs()]

    def to_pandas_refs(self) -> List[Any]:
        """One ObjectRef per block as a DataFrame (reference:
        to_pandas_refs)."""
        return [_block_converter("pandas").remote(r)
                for r in self.iter_internal_block_refs()]

    def to_torch(self, *, label_column: Optional[str] = None,
                 feature_columns: Optional[List[str]] = None,
                 batch_size: int = 256, drop_last: bool = False):
        """Torch IterableDataset over this Dataset (reference: to_torch);
        yields (features[B, F], labels[B]) — or features only when no
        label_column is given."""
        import torch

        outer = self

        class _IterableTorch(torch.utils.data.IterableDataset):
            def __iter__(self):
                for batch in outer.iter_batches(batch_size=batch_size,
                                                drop_last=drop_last):
                    cols = feature_columns or [
                        c for c in batch if c != label_column]
                    feats = torch.stack(
                        [torch.as_tensor(
                            np.ascontiguousarray(batch[c]).astype(
                                np.float32))
                         for c in cols], dim=1)
                    if label_column is None:
                        yield feats
                    else:
                        # np.array copies: arrow-backed batches are
                        # read-only, which torch tensors must not alias
                        yield feats, torch.as_tensor(
                            np.array(batch[label_column]))

        return _IterableTorch()

    def to_dask(self):
        """dask.dataframe over this Dataset (reference: to_dask; requires
        dask — see also ray_tpu.util.dask for running dask graphs ON the
        cluster). Materializes through the driver."""
        try:
            import dask.dataframe as dd
        except ImportError as e:
            raise ImportError(
                "to_dask() requires dask (`pip install dask[dataframe]`)"
            ) from e
        return dd.from_pandas(self.to_pandas(),
                              npartitions=max(1, self.num_blocks()))

    def iterator(self) -> "DataIterator":
        """Iteration handle decoupled from the Dataset (reference:
        Dataset.iterator -> DataIterator, data/iterator.py:68)."""
        return DataIterator(self)

    def _new_stats(self):
        from ray_tpu.data._internal.stats import ExecutionStats

        stats = ExecutionStats()
        self._last_stats = stats
        return stats

    def iter_internal_block_refs(self) -> Iterator[Any]:
        stats = self._new_stats()
        try:
            yield from execute_refs(self._plan, stats=stats)
        finally:
            stats.finish()

    def iter_blocks(self) -> Iterator[Block]:
        stats = self._new_stats()
        try:
            yield from execute_streaming(self._plan, stats=stats)
        finally:
            stats.finish()

    def materialize(self) -> "MaterializedDataset":
        import ray_tpu

        refs = list(self.iter_internal_block_refs())
        blocks = ray_tpu.get(refs) if refs else []
        return MaterializedDataset(blocks)

    def iter_rows(self) -> Iterator[Dict[str, Any]]:
        for block in self.iter_blocks():
            yield from BlockAccessor.for_block(block).iter_rows()

    def iter_batches(self, *, batch_size: Optional[int] = 256,
                     batch_format: str = "numpy",
                     drop_last: bool = False) -> Iterator[Any]:
        leftover: Optional[Block] = None
        for block in self.iter_blocks():
            if leftover is not None and leftover.num_rows > 0:
                block = BlockAccessor.concat([leftover, block])
                leftover = None
            acc = BlockAccessor.for_block(block)
            n = acc.num_rows()
            if batch_size is None:
                if n:
                    yield acc.to_batch(batch_format)
                continue
            start = 0
            while n - start >= batch_size:
                yield BlockAccessor.for_block(
                    acc.slice(start, start + batch_size)
                ).to_batch(batch_format)
                start += batch_size
            if start < n:
                leftover = acc.slice(start, n)
        if leftover is not None and leftover.num_rows > 0 and not drop_last:
            yield BlockAccessor.for_block(leftover).to_batch(batch_format)

    def iter_jax_batches(self, *, batch_size: int = 256,
                         sharding=None, dtypes: Optional[Dict] = None,
                         drop_last: bool = True, prefetch: int = 1,
                         stats: Optional[Dict] = None
                         ) -> Iterator[Dict[str, Any]]:
        """numpy batches → global jax.Arrays, optionally sharded.

        With a ``NamedSharding`` (e.g. the trainer mesh's batch sharding
        from ``ray_tpu.train.batch_sharding()``), each yielded column is a
        GLOBAL array assembled from per-shard host slices device_put to
        exactly the devices that own them — the full batch is never
        replicated onto any device, and on a multi-host gang each process
        contributes only its local rows (its dataset shard) to the global
        batch, so the batch dim it yields is the PER-PROCESS slice of the
        global batch size.

        ``prefetch`` (default 1) double-buffers the device feed: a
        producer thread assembles batch N+1's host columns (block slicing,
        dtype casts — columns stay views over the object-store arena when
        blocks arrived zero-copy) and ISSUES its device transfer while the
        caller's compiled step consumes batch N, so input-pipeline work
        hides behind compute. ``prefetch=0`` restores the fully
        synchronous path (bit-identical batch stream, no extra thread).
        ``stats``, when a dict, is filled with produce_s / wait_s /
        batches / overlap_frac on exhaustion — the measured
        input-pipeline-overlap fraction.
        """
        import jax  # hoisted: ONE import for the whole iteration

        def to_device(batch: Dict[str, Any]) -> Dict[str, Any]:
            if dtypes:
                batch = {k: v.astype(dtypes[k]) if k in dtypes else v
                         for k, v in batch.items()}
            if sharding is not None:
                return {k: _shard_host_batch(v, sharding, _jax=jax)
                        for k, v in batch.items()}
            # one batched transfer for every column (device_put over the
            # dict pytree), not a synchronous per-column round trip
            return jax.device_put(batch)

        src = self.iter_batches(batch_size=batch_size,
                                batch_format="numpy",
                                drop_last=drop_last)
        if prefetch <= 0:
            # synchronous: every input-pipeline second is a consumer wait
            # second by definition — stats reflect that (overlap_frac 0)
            import time as _time

            from ray_tpu._private.device_profiler import observe_phase

            acc = {"produce_s": 0.0, "wait_s": 0.0, "batches": 0}
            try:
                it = iter(src)
                while True:
                    t0 = _time.perf_counter()
                    batch = next(it, _FEED_DONE)
                    if batch is _FEED_DONE:
                        break
                    out = to_device(batch)
                    dt = _time.perf_counter() - t0
                    acc["produce_s"] += dt
                    acc["wait_s"] += dt
                    observe_phase("input_wait", dt)
                    acc["batches"] += 1
                    yield out
            finally:
                if stats is not None:
                    stats.update(acc)
                    stats["overlap_frac"] = 0.0
            return
        yield from _prefetch_device_feed(src, to_device, prefetch, stats)

    def iter_torch_batches(self, *, batch_size: int = 256,
                           drop_last: bool = False) -> Iterator[Dict[str, Any]]:
        import torch

        for batch in self.iter_batches(batch_size=batch_size,
                                       batch_format="numpy",
                                       drop_last=drop_last):
            yield {k: torch.as_tensor(np.ascontiguousarray(v))
                   for k, v in batch.items()}

    def iter_tf_batches(self, *, batch_size: int = 256,
                        drop_last: bool = False) -> Iterator[Dict[str, Any]]:
        """numpy batches as tf tensors (reference: dataset.py
        iter_tf_batches)."""
        import tensorflow as tf

        for batch in self.iter_batches(batch_size=batch_size,
                                       batch_format="numpy",
                                       drop_last=drop_last):
            yield {k: tf.convert_to_tensor(v) for k, v in batch.items()}

    def to_tf(self, feature_columns, label_columns, *,
              batch_size: int = 256):
        """A tf.data.Dataset over (features, labels) tuples (reference:
        dataset.py to_tf). Columns may be a name or list of names; a single
        name yields the bare tensor, a list yields a dict."""
        import tensorflow as tf

        def norm(cols):
            return [cols] if isinstance(cols, str) else list(cols)

        fcols, lcols = norm(feature_columns), norm(label_columns)
        probe = next(
            self.iter_batches(batch_size=2, batch_format="numpy"), None)
        if probe is None:
            raise ValueError("to_tf cannot infer a schema from an empty "
                             "dataset")

        def spec(cols):
            specs = {
                c: tf.TensorSpec(
                    shape=(None,) + probe[c].shape[1:],
                    dtype=tf.as_dtype(probe[c].dtype))
                for c in cols}
            return specs[cols[0]] if len(cols) == 1 else specs

        def pick(batch, cols):
            if len(cols) == 1:
                return tf.convert_to_tensor(batch[cols[0]])
            return {c: tf.convert_to_tensor(batch[c]) for c in cols}

        def gen():
            for batch in self.iter_batches(batch_size=batch_size,
                                           batch_format="numpy"):
                yield pick(batch, fcols), pick(batch, lcols)

        return tf.data.Dataset.from_generator(
            gen, output_signature=(spec(fcols), spec(lcols)))

    # -- consumption ---------------------------------------------------------

    def take(self, limit: int = 20) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = []
        for row in self.iter_rows():
            out.append(row)
            if len(out) >= limit:
                break
        return out

    def take_all(self) -> List[Dict[str, Any]]:
        return list(self.iter_rows())

    def show(self, limit: int = 20) -> None:
        for row in self.take(limit):
            print(row)

    def count(self) -> int:
        return sum(
            BlockAccessor.for_block(b).num_rows() for b in self.iter_blocks())

    def schema(self):
        for block in self.iter_blocks():
            if block.num_rows or block.num_columns:
                return BlockAccessor.for_block(block).schema()
        return None

    def columns(self) -> List[str]:
        s = self.schema()
        return list(s.names) if s is not None else []

    def to_pandas(self):
        import pandas as pd

        blocks = list(self.iter_blocks())
        if not blocks:
            return pd.DataFrame()
        return BlockAccessor.concat(blocks).to_pandas()

    def to_arrow(self):
        return BlockAccessor.concat(list(self.iter_blocks()))

    def to_numpy(self) -> Dict[str, np.ndarray]:
        return BlockAccessor.for_block(self.to_arrow()).to_numpy_batch()

    def stats(self) -> str:
        """Per-operator execution stats of the most recent consumption of
        this dataset (wall/cpu time, rows, bytes per operator — collected
        by the streaming executor). Executes the plan if this dataset was
        never consumed."""
        if self._last_stats is None:
            for _ in self.iter_blocks():
                pass
        if self._last_stats is None:  # e.g. MaterializedDataset override
            return ("already materialized; no per-op execution stats "
                    "recorded")
        return self._last_stats.to_string()

    # -- aggregates ----------------------------------------------------------

    def _agg_column(self, on: str, fn) -> Any:
        vals = [fn(BlockAccessor.for_block(b).to_numpy_batch()[on])
                for b in self.iter_blocks()
                if BlockAccessor.for_block(b).num_rows() > 0]
        return vals

    def sum(self, on: str):  # noqa: A003
        vals = self._agg_column(on, np.sum)
        return builtins.sum(vals) if vals else 0

    def min(self, on: str):  # noqa: A003
        vals = self._agg_column(on, np.min)
        return builtins.min(vals) if vals else None

    def max(self, on: str):  # noqa: A003
        vals = self._agg_column(on, np.max)
        return builtins.max(vals) if vals else None

    def mean(self, on: str):
        tot, cnt = 0.0, 0
        for b in self.iter_blocks():
            acc = BlockAccessor.for_block(b)
            if acc.num_rows():
                col = acc.to_numpy_batch()[on]
                tot += float(np.sum(col))
                cnt += len(col)
        return tot / cnt if cnt else None

    def std(self, on: str):
        arr = self.to_numpy().get(on)
        return float(np.std(arr, ddof=1)) if arr is not None and len(arr) > 1 \
            else None

    def unique(self, on: str) -> List[Any]:
        seen: List[Any] = []
        seen_set = set()
        for row in self.iter_rows():
            v = row[on]
            if v not in seen_set:
                seen_set.add(v)
                seen.append(v)
        return seen

    # -- splits --------------------------------------------------------------

    def split(self, n: int) -> List["MaterializedDataset"]:
        import ray_tpu

        refs = list(self.iter_internal_block_refs())
        blocks = ray_tpu.get(refs) if refs else []
        big = BlockAccessor.concat(blocks) if blocks else None
        if big is None:
            return [MaterializedDataset([]) for _ in builtins.range(n)]
        acc = BlockAccessor.for_block(big)
        total = acc.num_rows()
        per = total // n
        out = []
        for i in builtins.range(n):
            start = i * per
            end = total if i == n - 1 else (i + 1) * per
            out.append(MaterializedDataset([acc.slice(start, end)]))
        return out

    def split_at_indices(self, indices: List[int]) -> List["MaterializedDataset"]:
        big = self.to_arrow()
        acc = BlockAccessor.for_block(big)
        bounds = [0] + list(indices) + [acc.num_rows()]
        return [MaterializedDataset([acc.slice(bounds[i], bounds[i + 1])])
                for i in builtins.range(len(bounds) - 1)]

    def train_test_split(self, test_size: float, *, shuffle: bool = False,
                         seed: Optional[int] = None):
        ds: Dataset = self.random_shuffle(seed=seed) if shuffle else self
        big = ds.to_arrow()
        acc = BlockAccessor.for_block(big)
        n = acc.num_rows()
        n_test = int(n * test_size) if isinstance(test_size, float) else test_size
        return (MaterializedDataset([acc.slice(0, n - n_test)]),
                MaterializedDataset([acc.slice(n - n_test, n)]))

    def split_shard(self, rank: int, world_size: int) -> "Dataset":
        """Shard by read-task (and round-robin blocks) for per-train-worker
        consumption (reference: streaming_split dataset.py:1223 +
        train/_internal/data_config.py)."""
        tasks = self._plan.read_tasks
        if len(tasks) < world_size:
            # Fewer read tasks than workers: EVERY worker reads everything
            # and stride-filters rows by rank (consistent across ranks).
            shard = Dataset(Plan(tasks, list(self._plan.operators)))

            def stride(batch: Dict[str, np.ndarray]):
                return {k: v[rank::world_size] for k, v in batch.items()}

            return shard.map_batches(stride)
        my_tasks = [t for i, t in enumerate(tasks) if i % world_size == rank]
        return Dataset(Plan(my_tasks, list(self._plan.operators)))

    def streaming_split(self, n: int, *, equal: bool = False,
                        locality_hints=None) -> List["Dataset"]:
        return [self.split_shard(i, n) for i in builtins.range(n)]

    # -- writes --------------------------------------------------------------

    def _write(self, path: str, writer: Callable, extension: str) -> None:
        import os
        import uuid

        os.makedirs(path, exist_ok=True)
        run_id = uuid.uuid4().hex[:6]

        for i, block in enumerate(self.iter_blocks()):
            if block.num_rows == 0:
                continue
            writer(block,
                   os.path.join(path, f"part-{run_id}-{i:05d}{extension}"))

    def write_parquet(self, path: str, **_kw) -> None:
        import pyarrow.parquet as pq

        self._write(path, lambda b, p: pq.write_table(b, p), ".parquet")

    def write_csv(self, path: str, **_kw) -> None:
        from pyarrow import csv as pacsv

        self._write(path, lambda b, p: pacsv.write_csv(b, p), ".csv")

    def write_json(self, path: str, **_kw) -> None:
        def w(block, p):
            with open(p, "w") as f:
                block.to_pandas().to_json(f, orient="records", lines=True)

        self._write(path, w, ".json")

    def write_numpy(self, path: str, *, column: str, **_kw) -> None:
        def w(block, p):
            batch = BlockAccessor.for_block(block).to_numpy_batch()
            np.save(p, batch[column])

        self._write(path, w, ".npy")

    def write_tfrecords(self, path: str, **_kw) -> None:
        """tf.train.Example TFRecords via the built-in codec (no TF)."""
        from ray_tpu.data._internal import tfrecords as tfr

        def w(block, p):
            with open(p, "wb") as f:
                for row in BlockAccessor.for_block(block).iter_rows():
                    tfr.write_record(f, tfr.encode_example(row))

        self._write(path, w, ".tfrecords")

    def write_webdataset(self, path: str, **_kw) -> None:
        """WebDataset tar shards: row["__key__"] names the sample (generated
        if absent); each other column becomes `<key>.<column>` with bytes /
        utf-8 content."""
        import io
        import tarfile

        def w(block, p):
            with tarfile.open(p, "w") as tf:
                for i, row in enumerate(
                        BlockAccessor.for_block(block).iter_rows()):
                    key = str(row.pop("__key__", f"sample{i:06d}"))
                    for col, value in row.items():
                        if isinstance(value, np.ndarray):
                            # .npy bytes — full-fidelity (str() would
                            # truncate); np.load(BytesIO(...)) recovers it
                            buf = io.BytesIO()
                            np.save(buf, value)
                            value = buf.getvalue()
                        elif not isinstance(value, bytes):
                            value = str(value).encode()
                        info = tarfile.TarInfo(f"{key}.{col}")
                        info.size = len(value)
                        tf.addfile(info, io.BytesIO(value))

        self._write(path, w, ".tar")

    def write_images(self, path: str, *, column: str,
                     file_format: str = "png", **_kw) -> None:
        """Write the image column as one file per row (reference:
        write_images; requires pillow)."""
        try:
            from PIL import Image  # noqa: F401
        except ImportError as e:
            raise ImportError("write_images requires pillow") from e

        def w(block, p):
            from PIL import Image as PILImage

            batch = BlockAccessor.for_block(block).to_numpy_batch()
            base, _ = p.rsplit(".", 1)
            for i, arr in enumerate(batch[column]):
                PILImage.fromarray(np.asarray(arr)).save(
                    f"{base}-{i:06d}.{file_format}")

        self._write(path, w, f".{file_format}")

    def write_mongo(self, *, uri: str, database: str, collection: str,
                    **_kw) -> None:
        """Insert rows into MongoDB (reference: write_mongo; requires
        pymongo)."""
        try:
            import pymongo  # noqa: F401
        except ImportError as e:
            raise ImportError("write_mongo requires pymongo") from e

        def insert(batch: Dict[str, np.ndarray]):
            import pymongo as pm

            client = pm.MongoClient(uri)
            rows = [dict(zip(batch.keys(), vals))
                    for vals in builtins.zip(*[v.tolist()
                                               for v in batch.values()])]
            client[database][collection].insert_many(rows)
            client.close()
            return batch

        # runs distributed like any map stage; output discarded
        for _ in self.map_batches(insert).iter_blocks():
            pass

    def write_bigquery(self, *, project_id: str, dataset: str,
                       **_kw) -> None:
        """Write to a BigQuery table (reference: write_bigquery; requires
        google-cloud-bigquery)."""
        try:
            from google.cloud import bigquery  # noqa: F401
        except ImportError as e:
            raise ImportError(
                "write_bigquery requires google-cloud-bigquery") from e

        def load(batch: Dict[str, np.ndarray]):
            import pandas as pd
            from google.cloud import bigquery as bq

            client = bq.Client(project=project_id.split(".")[0])
            client.load_table_from_dataframe(
                pd.DataFrame({k: v.tolist() for k, v in batch.items()}),
                f"{project_id}.{dataset}").result()
            return batch

        for _ in self.map_batches(load).iter_blocks():
            pass

    def write_datasource(self, datasource, **kwargs) -> None:
        """Custom sink: an object with write(block_iterator, **kwargs)
        (reference: Dataset.write_datasource / Datasource.write)."""
        datasource.write(self.iter_blocks(), **kwargs)

    def write_sql(self, sql: str, connection_factory: Callable, **_kw) -> None:
        """Run a parameterized INSERT per row over a DBAPI connection
        (reference: dataset.py write_sql — e.g. "INSERT INTO t VALUES (?, ?)")."""
        def bindable(v):
            if isinstance(v, np.generic):
                return v.item()
            if isinstance(v, np.ndarray):
                # DBAPI drivers can't bind arrays; store round-trippable
                # .npy bytes (np.load(BytesIO(blob)) recovers the tensor)
                import io

                buf = io.BytesIO()
                np.save(buf, v)
                return buf.getvalue()
            return v

        conn = connection_factory()
        try:
            cur = conn.cursor()
            for block in self.iter_blocks():
                acc = BlockAccessor.for_block(block)
                cur.executemany(sql, [tuple(bindable(v)
                                            for v in r.values())
                                      for r in acc.iter_rows()])
            conn.commit()
        finally:
            conn.close()

    # -- misc ----------------------------------------------------------------

    def num_blocks(self) -> int:
        return len(self._plan.read_tasks)

    def __repr__(self):
        ops = " -> ".join(o.kind for o in self._plan.operators) or "read"
        return (f"Dataset(read_tasks={len(self._plan.read_tasks)}, "
                f"plan={ops})")


class MaterializedDataset(Dataset):
    """A dataset whose blocks are already computed (reference:
    MaterializedDataset in dataset.py — returned by materialize())."""

    def __init__(self, blocks: List[Block]):
        self._blocks = blocks
        tasks = [(lambda b=b: [b]) for b in blocks]
        super().__init__(Plan(tasks, []))

    def iter_blocks(self) -> Iterator[Block]:
        yield from self._blocks

    def count(self) -> int:
        return builtins.sum(b.num_rows for b in self._blocks)


# ---- module-level helpers for the breadth API ------------------------------

_BLOCK_CONVERTERS: Dict[str, Any] = {}


def _block_converter(kind: str):
    """Memoized remote block converters (fresh wrappers per call would mint
    new function ids and forfeit lease caching — see ADVICE r2)."""
    if kind not in _BLOCK_CONVERTERS:
        import ray_tpu

        if kind == "numpy":
            def convert(block):
                return BlockAccessor.for_block(block).to_numpy_batch()
        else:
            def convert(block):
                return BlockAccessor.for_block(block).to_pandas()

        _BLOCK_CONVERTERS[kind] = ray_tpu.remote(convert)
    return _BLOCK_CONVERTERS[kind]


class DataIterator:
    """Iteration facade over a Dataset (reference: data/iterator.py:68 —
    what `streaming_split` shards and `Dataset.iterator()` hand out)."""

    def __init__(self, dataset: Dataset):
        self._ds = dataset

    def iter_rows(self):
        return self._ds.iter_rows()

    def iter_batches(self, **kwargs):
        return self._ds.iter_batches(**kwargs)

    def iter_torch_batches(self, **kwargs):
        return self._ds.iter_torch_batches(**kwargs)

    def iter_jax_batches(self, **kwargs):
        return self._ds.iter_jax_batches(**kwargs)

    def materialize(self):
        return self._ds.materialize()
