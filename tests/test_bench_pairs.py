"""tools/bench_pairs.py: the order of the runs, on no chip.

The runs themselves (a checkout's `benchmarks/harness.run_cell` in a
process of its own) need a TPU; here `_run` is a stub and what is
checked is what the comparison rests on: parent and change alternate,
the two sides of a pair share a seed and no two pairs do, a warm-up
run is not counted, and the file holds every counted run.
"""

import importlib.util
import json
import os

import pytest

TOOL = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "bench_pairs.py")


@pytest.fixture
def tool(monkeypatch):
    spec = importlib.util.spec_from_file_location("bench_pairs", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    calls = []

    def run(side, checkout, cell, seed, trace, seconds, tmp):
        calls.append((side, os.path.basename(checkout), cell, seed, trace))
        return {"side": side, "cell": cell, "seed": seed, "trace": trace,
                "rc": 0, "wall_s": 0.0, "record": None,
                "result": {"correct": True, "metrics": {
                    "tokens_per_s": {"value": 1.0, "unit": "tokens/s"}}}}

    monkeypatch.setattr(mod, "_run", run)
    return mod, calls


@pytest.mark.parametrize("warm", [False, True])
def test_pairs_alternate_and_share_a_seed(tool, tmp_path, capsys, warm):
    mod, calls = tool
    out = tmp_path / "o" / "pairs.json"
    argv = ["--parent", str(tmp_path / "_parent"), "--out", str(out),
            "--seed0", "2147483901", "a:0:3", "a:1:1", "b:0:1"]
    assert mod.main(argv + ["--warm"] * warm) == 0
    sides = "".join(c[0] for c in calls)
    if warm:    # one throw-away run a side of each CELL, not each spec
        assert sides == "pc" + "pccppc" + "pc" + "pc" + "pc"
        counted = calls[2:10] + calls[12:]
    else:
        assert sides == "pccppc" + "pc" + "pc"
        counted = calls
    assert {c[1] for c in calls if c[0] == "p"} == {"_parent"}
    assert {c[1] for c in calls if c[0] == "c"} == {
        os.path.basename(os.path.dirname(os.path.dirname(TOOL)))}
    seeds = [c[3] for c in counted]
    assert seeds[0::2] == seeds[1::2]                 # a pair shares one
    assert len(set(seeds)) == len(seeds) // 2         # no two pairs do
    assert min(c[3] for c in calls) == 2147483901
    rows = json.loads(out.read_text())
    assert [(r["side"], r["cell"], r["seed"], r["trace"]) for r in rows] \
        == [(c[0],) + c[2:] for c in counted]
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["counted"] for x in lines].count(False) == (4 if warm else 0)
    assert all(x["tokens_per_s"] == 1.0 for x in lines)
