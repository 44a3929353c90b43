"""The loader-fed data kind is in no cell yet (PERF.md section 7): this keeps
its plain rebuild true to the program's ``imagenet`` pipeline."""

from harness import manifest


def test_the_rebuild_is_the_pipelines_output_bit_for_bit(tmp_path):
    train = manifest.load_module("drivers", "train")
    shards = manifest.load_module("data", "imagenet_shards")
    params = {"shards": 2, "rows_per_shard": 8, "row_shape": [40, 40, 3]}
    config = {"input_shape": [32, 32, 3], "batch_size": 4}
    inner = shards.make(11, params, config, str(tmp_path))
    got = []
    for epoch in range(2):  # 4 batches an epoch: the rebuild crosses into the second
        got += list(inner.train_epoch(epoch, 4, seed=5))
    want = shards.reference_batches(11, params, config, 5, 6)
    assert train.batches_differ(got[:6], want) == 0
    assert train.batches_differ(got[1:7], want) == 6
