import os
import re

from harness import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_entry_finds_its_files_by_name():
    man = manifest.load_manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    e2e = {m["name"] for m in man["end_to_end"]}
    for w in man["workloads"]:
        _, cell, workload, config = manifest.resolve(w["name"])
        assert manifest.load_module("drivers", workload["driver"]) is not None
        assert manifest.load_module("data", workload["data"]["kind"]) is not None
        assert manifest.load_module("reference", config["name"]) is not None
        assert manifest.load_module("flops", config["name"]) is not None
        assert len(w["why"]) <= 200 and NAME.match(w["name"]) and NAME.match(w["traffic"])
    cells = {w["name"] for w in man["workloads"]}
    for m in man["per_layer"]:
        assert manifest.load_module("metrics", m["name"]) is not None, m["name"]
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
    for c in man["configs"]:
        assert os.path.exists(os.path.join(manifest.ROOT, c["file"])) and len(c["why"]) <= 200
        assert len(c["source"]) <= 200
