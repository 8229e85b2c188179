"""Every cell of BENCHMARK.json resolves its files by name, and the file
keeps to the contract's shape."""
import json
import re

import pytest

from bench.spec import BENCH, ROOT, load_benchmark, resolve

BENCHMARK = load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_cell_resolves(cell):
    c = resolve(cell)
    assert c.chips in (1, 4)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert hasattr(c.readers[m["name"]], "read")
    for fn in ("init_params", "loss", "products", "n_params"):
        assert callable(getattr(c.reference, fn))
    lims = {k: v for k, v in c.limits.items() if not k.startswith("_")}
    assert lims and all(v["lower"] < v["limit"] < v["upper"]
                        for v in lims.values())
    e = c.traffic["elastic"]
    assert c.traffic["check_chunks"] * c.traffic["rounds_per_call"] <= (
        c.traffic["pool_rounds"])


def test_names_and_files():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCHMARK[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    files = [c["file"] for c in BENCHMARK["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert f.startswith("bench/") and (ROOT / f).is_file()
        cfg = json.loads((ROOT / f).read_text())
        assert (ROOT / f).with_suffix(".py").is_file()
        assert cfg["name"] in {c["name"] for c in BENCHMARK["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCHMARK["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCHMARK["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
    for m in BENCHMARK["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in BENCHMARK["end_to_end"]}
    four = sum(w["chips"] == 4 for w in BENCHMARK["workloads"])
    assert four <= max(1, len(BENCHMARK["workloads"]) // 2)
