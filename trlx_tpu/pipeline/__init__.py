"""Pipeline registry, base dataset/store classes, a lightweight numpy
DataLoader, and the minibatch iterator.

Parity: trlx/pipeline/__init__.py (register_datapipeline/_DATAPIPELINE,
BasePipeline/BaseRolloutStore with create_loader, MiniBatchIterator
:105-177). The reference builds on torch Dataset/DataLoader; here data prep
is host-side numpy feeding jit-compiled steps, so we ship our own minimal
loader (shuffling, collation, drop_last) with no torch dependency.
"""

import random
import sys
from abc import abstractmethod
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import numpy as np

from trlx_tpu.observability import tracing
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

# Pipeline registry, keyed by lowercased class name.
_DATAPIPELINE: Dict[str, Any] = {}


def register_datapipeline(name):
    """Decorator to register a pipeline class (reference pipeline/__init__.py:14-38)."""

    def register_class(cls, name):
        _DATAPIPELINE[name] = cls
        setattr(sys.modules[__name__], name, cls)
        return cls

    if isinstance(name, str):
        name = name.lower()
        return lambda c: register_class(c, name)

    cls = name
    register_class(cls, cls.__name__.lower())
    return cls


class DataLoader:
    """Minimal host-side batch loader over a list-like dataset.

    Yields collated batches; `collate_fn` defaults to numpy stacking of
    dict fields. Deterministic shuffling via a seed bumped per epoch.

    `group_window` > `batch_size` with a `group_key` (index -> sort key)
    keeps the epoch's order in windows of that many items and stable-sorts
    each window by the key before it is cut into batches: a window holds
    the items it would have held, and items of like key share a batch.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        shuffle: bool = False,
        collate_fn: Optional[Callable] = None,
        drop_last: bool = False,
        seed: int = 0,
        group_window: int = 0,
        group_key: Optional[Callable[[int], Any]] = None,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.collate_fn = collate_fn or default_collate
        self.drop_last = drop_last
        self.seed = seed
        self.group_window = group_window if group_key is not None else 0
        self.group_key = group_key
        self._epoch = 0

    def __len__(self):
        n = len(self.dataset)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size

    def __iter__(self):
        return self.batches()

    def batches(self, skip: int = 0, epoch: Optional[int] = None):
        """One epoch's batches; the first `skip` are passed over uncollated.
        `epoch` names the epoch (the shuffle's seed is `seed + epoch`); left
        out, it is the one after the last."""
        if epoch is not None:
            self._epoch = epoch
        indices = list(range(len(self.dataset)))
        if self.shuffle:
            rng = random.Random(self.seed + self._epoch)
            rng.shuffle(indices)
            self._epoch += 1
        if self.group_window > self.batch_size:
            w = self.group_window
            indices = [i for start in range(0, len(indices), w)
                       for i in sorted(indices[start : start + w], key=self.group_key)]
        for start in range(skip * self.batch_size, len(indices), self.batch_size):
            chunk = indices[start : start + self.batch_size]
            if self.drop_last and len(chunk) < self.batch_size:
                break
            with tracing.span("pipeline.collate", rows=len(chunk)):
                batch = self.collate_fn([self.dataset[i] for i in chunk])
            yield batch


class LoaderStream:
    """`infinite_dataloader` that knows where it is: batches forever, the
    loader restarted at exhaustion, with the epoch under way and the
    batches handed out of it as a `state()` that `restore()` returns to
    (exact resume: the same batches after a restart as before it)."""

    def __init__(self, loader: DataLoader):
        self.loader = loader
        self.epoch = self.position = 0
        self._batches = None

    def __iter__(self):
        return self

    def __next__(self):
        while True:
            if self._batches is None:
                self._batches = self.loader.batches(skip=self.position, epoch=self.epoch)
            batch = next(self._batches, None)
            if batch is not None:
                self.position += 1
                return batch
            if not self.position:
                raise StopIteration  # a loader with no batch at all
            self._batches, self.epoch, self.position = None, self.epoch + 1, 0

    def state(self) -> Dict[str, int]:
        return {"epoch": self.epoch, "position": self.position}

    def restore(self, state: Dict[str, int]) -> None:
        self.epoch, self.position = int(state["epoch"]), int(state["position"])
        self._batches = None


def default_collate(items: List[Any]):
    """Stack a list of dicts / dataclasses / arrays into a batch."""
    if isinstance(items[0], dict):
        return {k: default_collate([it[k] for it in items]) for k in items[0]}
    if hasattr(items[0], "__dataclass_fields__"):
        cls = type(items[0])
        fields = items[0].__dataclass_fields__.keys()
        return cls(**{f: default_collate([getattr(it, f) for it in items]) for f in fields})
    first = items[0]
    if isinstance(first, (np.ndarray, int, float, np.integer, np.floating)):
        return np.stack([np.asarray(x) for x in items])
    return items  # lists of strings / metadata pass through


class BasePipeline:
    """Dataset of prompts / samples (reference pipeline/__init__.py:42-68)."""

    def __init__(self, path: str = "dataset"):
        self.path = path

    @abstractmethod
    def __getitem__(self, index: int):
        pass

    @abstractmethod
    def __len__(self) -> int:
        pass

    @abstractmethod
    def create_loader(self, batch_size: int, shuffle: bool, **kwargs) -> DataLoader:
        pass


class BaseRolloutStore:
    """Rollout storage (reference pipeline/__init__.py:71-102)."""

    def __init__(self, capacity=-1):
        self.history: Iterable[Any] = None
        self.capacity = capacity

    @abstractmethod
    def push(self, exps: Iterable[Any]):
        """Push experiences to the store."""
        pass

    def __getitem__(self, index: int):
        return self.history[index]

    def __len__(self) -> int:
        return len(self.history)

    @abstractmethod
    def create_loader(self, batch_size: int, shuffle: bool, **kwargs) -> DataLoader:
        pass


def slice_tree(batch, start: int, stop: int):
    """Slice every array leaf of a batch pytree along the leading axis;
    non-array leaves (e.g. string lists) are sliced as sequences."""

    def _slice(x):
        if isinstance(x, (np.ndarray, jax.Array)):
            return x[start:stop]
        if isinstance(x, (list, tuple)):
            return x[start:stop]
        return x

    if isinstance(batch, dict):
        return {k: _slice(v) if not isinstance(v, dict) else slice_tree(v, start, stop) for k, v in batch.items()}
    if hasattr(batch, "__dataclass_fields__"):
        cls = type(batch)
        return cls(
            **{f: slice_tree(getattr(batch, f), start, stop) if isinstance(getattr(batch, f), dict) else _slice(getattr(batch, f)) for f in batch.__dataclass_fields__}
        )
    return _slice(batch)


def tree_batch_size(batch) -> int:
    # Plain dataclasses aren't registered pytrees — recurse into fields so
    # user-defined batch containers work (the reference sizes batches the
    # same way, pipeline/__init__.py:118-130).
    if hasattr(batch, "__dataclass_fields__") and not hasattr(batch, "shape"):
        for f in batch.__dataclass_fields__:
            n = tree_batch_size(getattr(batch, f))
            if n:
                return n
        return 0
    leaves = jax.tree_util.tree_leaves(batch)
    for leaf in leaves:
        if hasattr(leaf, "shape") and len(getattr(leaf, "shape", ())) > 0:
            return leaf.shape[0]
        if isinstance(leaf, (list, tuple)):
            return len(leaf)
    return 0


class MiniBatchIterator:
    """Split each dataloader batch into `num_mb` microbatches of `mb_size`,
    preserving the batch's container type (reference
    pipeline/__init__.py:105-177, including the ragged/empty warnings)."""

    def __init__(self, data_loader, mb_size: int, num_mb: int):
        self.data_loader = data_loader
        self.mb_size = mb_size
        self.num_mb = num_mb

    def __iter__(self):
        for batch in self.data_loader:
            total = tree_batch_size(batch)
            minibatches = []
            for mbi in range(self.num_mb):
                start, stop = mbi * self.mb_size, (mbi + 1) * self.mb_size
                if start >= total:
                    logger.warning(
                        "WARNING: MiniBatchIterator generated empty batch, increase dataset size "
                        "or decrease batch size"
                    )
                    break
                mb = slice_tree(batch, start, stop)
                actual = tree_batch_size(mb)
                if actual < self.mb_size:
                    logger.warning(
                        f"WARNING: Minibatch size {actual} is less than configured {self.mb_size}"
                    )
                minibatches.append(mb)
            if minibatches:
                yield minibatches
