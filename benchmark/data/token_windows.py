"""The benchmark's own seeded token windows, in the shape the program's
``lm_synthetic`` yields: batches ``(tokens, tokens)`` of int32 [B, T] sharing
one array (the model shifts the targets itself). Rows all differ."""

import numpy as np


class TokenWindows:
    def __init__(self, seed, windows, seq_len, vocab):
        self.seed = int(seed)
        self.image_shape = (int(seq_len),)
        self.n_classes = int(vocab)
        rng = np.random.default_rng(self.seed)
        self.x = rng.integers(0, vocab, size=(int(windows), int(seq_len)), dtype=np.int32)

    def train_epoch(self, epoch, batch_size, seed=0, part=None):
        perm = np.random.default_rng([self.seed, int(epoch)]).permutation(len(self.x))
        for i in range(len(self.x) // batch_size):
            idx = perm[i * batch_size:(i + 1) * batch_size]
            if part is not None:
                idx = idx[part]
            x = self.x[idx]
            yield x, x


def make(seed, params, config, workdir):
    return TokenWindows(seed, params["windows"], config["seq_len"], config["vocab"])
