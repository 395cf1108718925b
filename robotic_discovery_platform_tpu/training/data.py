"""Dataset loading and the host input pipeline.

Reference behavior preserved (scripts/train_segmenter.py:66-100): image/mask
pairing by identical filename, BGR->RGB, INTER_AREA resize for images and
INTER_NEAREST for masks to ``img_size``, /255 normalization, deterministic
80/20 split. TPU-first departures:

- **NHWC numpy batches** instead of per-sample CHW tensors; the jitted train
  step consumes whole batches.
- **Background prefetch**: the reference loads synchronously inside the train
  loop with ``num_workers=0`` (train_segmenter.py:138-139), starving the
  device; here a daemon thread decodes/augments the next batches while the
  TPU runs the current step (SURVEY.md section 3.3 "async host input
  pipeline").
- **Sharding-aware batching**: ``Batches`` can pad/trim to a global batch
  divisible by the data-parallel world size.
- **Full final batch**: jit needs static shapes, so a ragged last batch is
  filled by cyclically repeating the epoch's permutation (``epoch_order``)
  -- a handful of samples are seen twice per epoch. The reference instead
  yields a short ragged batch (torch DataLoader default); at the reference
  config (51 train images, batch 4) the difference is one duplicated
  sample per epoch.
"""

from __future__ import annotations

import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from robotic_discovery_platform_tpu.observability import instruments as obs


class PairedSegmentationData:
    """File-pair dataset (reference: SegmentationDataset,
    train_segmenter.py:66-100)."""

    def __init__(self, dataset_dir: str | Path, img_size: int = 256):
        self.root = Path(dataset_dir)
        self.img_size = img_size
        img_dir = self.root / "images"
        mask_dir = self.root / "masks"
        if not img_dir.is_dir() or not mask_dir.is_dir():
            raise FileNotFoundError(
                f"dataset at {self.root} needs images/ and masks/ subdirs "
                "(generate one with training.synthetic.generate_dataset)"
            )
        mask_names = {p.name for p in mask_dir.iterdir()}
        self.names = sorted(p.name for p in img_dir.iterdir() if p.name in mask_names)
        if not self.names:
            raise FileNotFoundError(f"no paired image/mask files in {self.root}")

    def __len__(self) -> int:
        return len(self.names)

    def load(self, name: str):
        import cv2

        img = cv2.imread(str(self.root / "images" / name), cv2.IMREAD_COLOR)
        mask = cv2.imread(str(self.root / "masks" / name), cv2.IMREAD_GRAYSCALE)
        if img is None or mask is None:
            raise IOError(f"failed to read pair {name!r}")
        s = self.img_size
        img = cv2.resize(img, (s, s), interpolation=cv2.INTER_AREA)[..., ::-1]
        mask = cv2.resize(mask, (s, s), interpolation=cv2.INTER_NEAREST)
        x = img.astype(np.float32) / 255.0
        y = (mask.astype(np.float32) / 255.0)[..., None]
        return x, y

    def as_arrays(self, names=None):
        names = self.names if names is None else names
        xs = np.zeros((len(names), self.img_size, self.img_size, 3), np.float32)
        ys = np.zeros((len(names), self.img_size, self.img_size, 1), np.float32)
        for i, n in enumerate(names):
            xs[i], ys[i] = self.load(n)
        return xs, ys


#: what a pixel code is divided by to become a unit float
UNIT_SCALE = 255.0

#: threads of :func:`unit_floats` (the pass is bound by memory and page
#: faults: beyond a handful they no longer add)
_UNIT_FLOAT_THREADS = 8


def unit_floats(a: np.ndarray) -> np.ndarray:
    """``np.asarray(a, np.float32) / 255.0`` to the last bit, for integer
    rows: one pass that casts and divides block by block (not a float32
    copy and then a quotient, two arrays four times the rows' size) on a
    few threads, since numpy's loops release the interpreter lock. A
    resident data set of 1,024 pairs at 256 x 256 took a ``train_model``
    call 2.4 s the other way, every call (PERF.md, PR 32)."""
    out = np.empty(a.shape, np.float32)
    rows = max(1, -(-len(a) // _UNIT_FLOAT_THREADS))

    def scale(start):
        np.divide(a[start:start + rows], UNIT_SCALE,
                  out=out[start:start + rows], dtype=np.float32)

    with ThreadPoolExecutor(_UNIT_FLOAT_THREADS) as pool:
        list(pool.map(scale, range(0, len(a), rows)))
    return out


class IntegerRows:
    """Integer rows narrower than float32 that a job trains on as float32,
    and what makes them that: ``unit`` for ``rows / 255`` (pixel codes, masks
    coded 0/255), otherwise a plain cast (masks coded 0/1). The task that
    prepared the data set states the rule (``tasks.UNetTask.prepare``); the
    floats are made where the job holds them. A job whose data set is
    resident on the device moves ``rows`` as the bytes they are, a quarter
    of the floats', and converts there (``trainer._ResidentScan.stage``);
    any other takes :meth:`floats` on the host.

    It describes the float32 rows it stands for (``shape``, ``dtype``,
    ``nbytes``, ``len``): what a job sizes and keys its programs by is what
    will be resident, not what arrived."""

    dtype = np.dtype(np.float32)

    def __init__(self, rows: np.ndarray, unit: bool):
        self.rows, self.unit = rows, unit
        self.shape = rows.shape
        self.nbytes = rows.size * self.dtype.itemsize

    def __len__(self) -> int:
        return len(self.rows)

    def floats(self) -> np.ndarray:
        """The float32 rows, on the host: :func:`unit_floats` or the cast."""
        return (unit_floats(self.rows) if self.unit
                else np.asarray(self.rows, np.float32))


def memory_view(rows: np.ndarray):
    """``(flat, (shape, axes))``: ``rows`` as the block of bytes the host
    holds, without a copy. ``flat`` is a C-contiguous two-dimensional view
    of ``rows.transpose(axes)``, the axes from the slowest-varying in
    memory to the fastest, and ``shape`` that transpose's shape, so that
    ``flat.reshape(shape).transpose(np.argsort(axes))`` is ``rows`` again:
    a consumer on the device undoes the order there, where it costs
    nothing, and the host re-lays nothing. For a C-contiguous array the
    axes are in order. An array fetched from a TPU is not (it keeps the
    device's order of dimensions: ``[n, h, w, c]`` images arrive with ``c``
    outside ``h``), and flattening it in index order would be a strided
    copy of every byte on one thread: 0.59 s for 1,024 uint8 pairs of 256 x
    256 (PERF.md section 6, PR 40). Rows that are no dense block in any
    order (a strided slice) are copied once, in index order."""
    rows = np.asarray(rows)
    axes = tuple(int(i) for i in np.argsort(
        [-abs(stride) for stride in rows.strides], kind="stable"))
    view = rows.transpose(axes)
    if not view.flags.c_contiguous:
        axes, view = tuple(range(rows.ndim)), np.ascontiguousarray(rows)
    return view.reshape(len(view), -1), (view.shape, axes)


def float_rows(rows: np.ndarray, unit: bool):
    """Integer ``rows`` as the float32 a job trains on (``rows / 255`` if
    ``unit``, else the cast): :class:`IntegerRows`, made later where the job
    holds them, for an integer narrower than float32, since moving those is
    moving fewer bytes; host floats at once for a wider one."""
    late = IntegerRows(rows, unit)
    return late if rows.dtype.itemsize < late.dtype.itemsize else late.floats()


def host_rows(rows) -> np.ndarray:
    """What a job that feeds its batches from the host indexes: ``rows``
    themselves, or the floats :class:`IntegerRows` stand for."""
    return rows.floats() if isinstance(rows, IntegerRows) else rows


def train_val_split(n: int, val_fraction: float, seed: int = 0):
    """Deterministic shuffled split (reference uses torch random_split 80/20,
    train_segmenter.py:134-136)."""
    order = np.random.default_rng(seed).permutation(n)
    n_val = max(1, int(round(n * val_fraction))) if n > 1 else 0
    return order[n_val:], order[:n_val]


def _check_divisor(batch_size: int, divisor: int) -> None:
    if divisor > 1 and batch_size % divisor:
        raise ValueError(
            f"batch_size {batch_size} must be divisible by the "
            f"data-parallel world size {divisor}"
        )


def epoch_order(n: int, batch_size: int, shuffle: bool,
                rng: np.random.Generator) -> np.ndarray:
    """(n_batches, batch_size) index matrix covering [0, n) with wrap-around
    tail padding so every batch is full — jit shapes stay static."""
    order = np.arange(n)
    if shuffle:
        rng.shuffle(order)
    n_batches = max(1, int(np.ceil(n / batch_size)))
    if n_batches * batch_size != n:
        # np.resize repeats the permutation cyclically, so splits smaller
        # than the pad amount still fill every slot
        order = np.resize(order, n_batches * batch_size)
    return order.reshape(n_batches, batch_size)


def _prefetched(producer_batches, make_item, prefetch: int):
    """Run ``make_item`` over ``producer_batches`` in a daemon thread, keeping
    up to ``prefetch`` finished batches queued ahead of the consumer.

    Producer errors re-raise on the consumer side; if the consumer abandons
    the iterator mid-epoch (train step raised, caller broke out), the
    ``cancel`` event unblocks the producer so the thread and its queued
    batches are released instead of pinned for the process lifetime."""
    q: queue.Queue = queue.Queue(maxsize=prefetch)
    stop = object()
    cancel = threading.Event()
    err: list[BaseException] = []

    def _put(item) -> bool:
        while not cancel.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def producer():
        try:
            for b in producer_batches:
                if cancel.is_set() or not _put(make_item(b)):
                    return
        except BaseException as e:  # surfaced on the consumer side
            err.append(e)
        finally:
            _put(stop)

    worker = threading.Thread(target=producer, name="batch-prefetch",
                              daemon=True)
    worker.start()
    try:
        while True:
            item = q.get()
            if item is stop:
                if err:
                    raise err[0]
                break
            yield item
    finally:
        cancel.set()
        # the cancel event unblocks a producer stuck on a full queue, so
        # this join is bounded: the thread (and its queued batches) is
        # actually released before the consumer moves on, instead of
        # lingering for the process lifetime
        worker.join(timeout=5)


class Batches:
    """Epoch iterator over in-memory arrays with shuffling, optional
    divisibility padding, and background prefetch."""

    def __init__(self, xs, ys, batch_size: int, shuffle: bool = True,
                 seed: int = 0, divisor: int = 1, prefetch: int = 2):
        if len(xs) == 0:
            raise ValueError("empty dataset")
        _check_divisor(batch_size, divisor)
        self.xs, self.ys = xs, ys
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.rng = np.random.default_rng(seed)
        self.prefetch = prefetch

    def __iter__(self):
        batches = epoch_order(len(self.xs), self.batch_size, self.shuffle,
                              self.rng)
        if self.prefetch <= 0:
            for idx in batches:
                yield self.xs[idx], self.ys[idx]
            return
        yield from _prefetched(
            batches, lambda idx: (self.xs[idx], self.ys[idx]), self.prefetch
        )

    def __len__(self):
        return max(1, int(np.ceil(len(self.xs) / self.batch_size)))


class StreamingBatches:
    """Decode-on-the-fly epoch iterator over a file-backed dataset subset.

    Constant-memory replacement for ``dataset.as_arrays()`` + ``Batches``:
    only ``prefetch + 1`` decoded batches exist at any moment, so dataset
    size is bounded by disk, not host RAM. A thread pool decodes/resizes the
    next batches (``load`` is OpenCV → releases the GIL) while the device
    runs the current step — the async host input pipeline the reference
    lacks (its loader is synchronous in-loop with ``num_workers=0``,
    train_segmenter.py:138-139; SURVEY.md Phase 5 "per-host sharded input
    pipeline").

    Same epoch semantics as ``Batches``: shuffled wrap-around-padded full
    batches, divisor-aware for data-parallel sharding.
    """

    def __init__(self, dataset: PairedSegmentationData, indices,
                 batch_size: int, shuffle: bool = True, seed: int = 0,
                 divisor: int = 1, prefetch: int = 2, workers: int = 4):
        indices = np.asarray(indices)
        if len(indices) == 0:
            raise ValueError("empty dataset subset")
        _check_divisor(batch_size, divisor)
        self.dataset = dataset
        self.names = [dataset.names[i] for i in indices]
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.rng = np.random.default_rng(seed)
        self.prefetch = max(1, prefetch)
        self.workers = max(1, workers)

    def _decode_batch(self, pool: ThreadPoolExecutor, idx: np.ndarray):
        s = self.dataset.img_size
        # on the prefetch thread: one batch's decode + resize, fanned out
        # over the pool
        with obs.TRAIN_PHASES.stage("rdp.loader.decode", timeline=False):
            xs = np.empty((len(idx), s, s, 3), np.float32)
            ys = np.empty((len(idx), s, s, 1), np.float32)
            loaded = pool.map(
                self.dataset.load, (self.names[i] for i in idx))
            for i, (x, y) in enumerate(loaded):
                xs[i], ys[i] = x, y
        return xs, ys

    def __iter__(self):
        batches = epoch_order(len(self.names), self.batch_size, self.shuffle,
                              self.rng)
        with ThreadPoolExecutor(max_workers=self.workers) as pool:
            yield from _prefetched(
                batches, lambda idx: self._decode_batch(pool, idx),
                self.prefetch,
            )

    def __len__(self):
        return max(1, int(np.ceil(len(self.names) / self.batch_size)))


# -- token data sets and block-diffusion noise --------------------------------

#: streams of :func:`block_diffusion_noise`: training steps draw from the
#: first, validation from the second
TRAIN_NOISE, EVAL_NOISE = 0, 1


def token_arrays(tokens) -> np.ndarray:
    """A resident token data set: ``[n, L]`` int32, sequences full (packed,
    no padding; attention crosses document boundaries inside a sequence)."""
    tokens = np.asarray(tokens)
    if tokens.ndim != 2 or not np.issubdtype(tokens.dtype, np.integer):
        raise ValueError(
            f"a token data set is [n, L] integers, got {tokens.dtype} "
            f"{tokens.shape}")
    return np.ascontiguousarray(tokens, np.int32)


def block_diffusion_noise(seed, stream: int, index, batch: int,
                          seq_len: int, block: int):
    """The noise of one batch, by a rule a reference can re-derive from
    ``jax.random`` alone::

        key    = fold_in(fold_in(jax.random.key(seed), stream), index)
        kt, ku = jax.random.split(key)
        t      = 1 - uniform(kt, [batch, seq_len / block])    in (0, 1]
        masked = uniform(ku, [batch, seq_len]) < t of the position's block

    ``stream`` is :data:`TRAIN_NOISE` with ``index`` the number of optimiser
    steps the job has taken before this one (over all its epochs and calls:
    a resumed job continues the sequence), or :data:`EVAL_NOISE` with
    ``index`` 0: every validation batch of every epoch sees the same noise,
    so validation losses compare across epochs. Returns ``(masked [batch,
    seq_len] bool, t [batch, seq_len] float32)``; ``seed`` (a 32-bit
    integer) and ``index`` may be traced: a traced seed gives the key of the
    same number, so one compiled step serves every job.
    """
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed), stream), index)
    kt, ku = jax.random.split(key)
    t = 1.0 - jax.random.uniform(kt, (batch, seq_len // block), jnp.float32)
    t = jnp.repeat(t, block, axis=1)
    masked = jax.random.uniform(ku, (batch, seq_len), jnp.float32) < t
    return masked, t
