import time

from harness.window_dataset import WindowDataset


class Inner:
    image_shape = (4,)
    n_classes = 3

    def __init__(self, per_epoch=3, pace=0.01):
        self.per_epoch, self.pace, self.epochs = per_epoch, pace, []

    def train_epoch(self, epoch, batch_size, seed=0, part=None):
        self.epochs.append(epoch)
        for i in range(self.per_epoch):
            time.sleep(self.pace)
            yield (epoch, i), (epoch, i)


def test_ends_within_one_batch_of_the_deadline_and_cycles_epochs():
    inner = Inner()
    w = WindowDataset(inner, seconds=0.2, warmup=2, keep_first=3)
    got = list(w.train_epoch(0, 8, seed=1))
    steps, seconds = w.window()
    assert steps == len(got) - 1 - 2
    assert 0.2 <= seconds < 0.2 + 2 * inner.pace + 0.01
    assert inner.epochs == list(range(len(inner.epochs))) and len(inner.epochs) > 3
    assert w.first == got[:3]
    assert w.n_train_batches(8) >= 10 ** 6 and w.n_val == 0 and list(w.val_epoch(8)) == []


def test_a_window_with_no_step_reports_none():
    w = WindowDataset(Inner(), seconds=0.0, warmup=5)
    w.stamps = [0.0, 1.0]
    assert w.window() == (0, 0.0)
