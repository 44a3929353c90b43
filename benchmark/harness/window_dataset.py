"""The clock that ends the run: a dataset that wraps a cell's real input
pipeline, presents one long epoch, stamps every batch it hands over and
stops yielding when ``seconds`` have passed since the window opened.

``run_training`` has no time limit and no per-step hook; it does take its
data by registered name and pulls batches through ``PrefetchLoader``. So
this wrapper drives the real driver, engine, loader thread and dispatcher
for ``seconds`` seconds with no edit to the program.

The first ``warmup`` batches are set-up (compile or cache load, first
touch). The window opens at the stamp of batch ``warmup`` and closes at the
first stamp at or after ``seconds`` later. With the loader ahead, the
producer blocks on the bounded queue, so stamp ``k + depth + 1`` is taken
when step ``k`` is taken; with the loader behind, each batch is stamped as
it is made. Either way the stamps advance at the pipeline's pace, shifted
by the same few batches at both ends, and

    train_step_ms = (t[last] - t[warmup]) / (last - warmup)

is all the steps over all the time.
"""

import time

EPOCH_BATCHES = 1_000_000  # the clock, not the count, ends the epoch


class WindowDataset:
    """Duck-types the program's ``Dataset``: the attributes and the two
    epoch iterators that ``run_training`` reads."""

    name = "bench_window"

    def __init__(self, inner, seconds, warmup, compile_clock=None, keep_first=0):
        self.inner = inner
        self.seconds = float(seconds)
        self.warmup = int(warmup)
        self.clock = compile_clock
        self.keep_first = int(keep_first)
        self.image_shape = tuple(inner.image_shape)
        self.n_classes = int(inner.n_classes)
        self.n_val = 0  # no validation pass: it would only lengthen the run
        self.val_views = 1
        self.device_transform = getattr(inner, "device_transform", None)
        self.stamps = []
        self.first = []  # the first batches as handed to the loader
        self.inner_epochs = 0
        self.programs_at_open = None
        self.programs_at_close = None
        self.compile_s_at_open = None

    @property
    def n_train(self):
        return EPOCH_BATCHES * 1024

    def n_train_batches(self, batch_size):
        return EPOCH_BATCHES

    def n_val_batches(self, batch_size):
        return 0

    def val_epoch(self, batch_size, part=None):
        return iter(())

    def train_epoch(self, epoch, batch_size, seed=0, part=None):
        deadline = None
        k = epoch
        while True:
            n_before = len(self.stamps)
            for x, y in self.inner.train_epoch(k, batch_size, seed=seed, part=part):
                now = time.perf_counter()
                i = len(self.stamps)
                self.stamps.append(now)
                if i < self.keep_first:
                    self.first.append((x, y))
                if i == self.warmup:
                    deadline = now + self.seconds
                    self.programs_at_open = self._programs()
                    self.compile_s_at_open = None if self.clock is None else float(self.clock.seconds)
                last = deadline is not None and i > self.warmup and now >= deadline
                if last:
                    self.programs_at_close = self._programs()
                yield x, y
                if last:
                    return
            if len(self.stamps) == n_before:
                raise RuntimeError("the inner dataset yields no batch of this size")
            k += 1
            self.inner_epochs += 1

    def _programs(self):
        return None if self.clock is None else int(self.clock.programs)

    # -- what the harness reads once run_training has returned ---------------
    def window(self):
        """-> (steps, seconds) of the measured window, or (0, 0.0)."""
        if len(self.stamps) <= self.warmup + 1:
            return 0, 0.0
        return (len(self.stamps) - 1 - self.warmup,
                self.stamps[-1] - self.stamps[self.warmup])
