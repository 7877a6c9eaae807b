"""The readers PR 24 added (layer_metrics/flash_fwd_ms, flash_bwd_ms,
run_prepare_ms, run_fetch_ms, feed_put_ms, feed_put_in_run_ms,
first_call_s): each on a made-up measurement and a made-up step record,
the two trace readers on a recorded chip trace from before the kernels
were named, every new BENCHMARK.json entry against its file, and one
tiny cell end to end on the CPU.  Nothing here is a measurement."""

import io
import json
import math
import os
import shutil

import pytest

import trace_reduce as tr
from conftest import BENCH, CHECKOUT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ["flash_fwd_ms", "flash_bwd_ms", "run_prepare_ms", "run_fetch_ms",
       "feed_put_ms", "feed_put_in_run_ms", "first_call_s"]
MS = 1_000_000


def _reader(name):
    import harness

    return harness._load_file(
        os.path.join(BENCH, "layer_metrics", name + ".py"))


# -- the step record's readers ------------------------------------------------

def _run(program, enter, first_call=False, fetched=True, prepare=2,
         enqueue=3, commit=1, fetch=100):
    """A `run` record whose phases last the given ms."""
    r = {"kind": "run", "program": program, "first_call": first_call,
         "fetched": fetched, "thread": 1, "enter": enter}
    t = enter
    for stamp, d in (("feeds", 0.25 * prepare), ("state", 0.25 * prepare),
                     ("key", 0.25 * prepare), ("built", 0),
                     ("conformed", 0.25 * prepare), ("dispatched", enqueue),
                     ("committed", commit),
                     ("returned", fetch if fetched else 0)):
        t += int(d * MS)
        r[stamp] = t
    return r


def _put(start, ms, thread=2):
    return {"kind": "put", "thread": thread, "start": start,
            "end": start + int(ms * MS), "bytes": 10, "host_wait": 0,
            "dev_wait": 0}


@pytest.fixture
def ring():
    """A made-up ring, as a run of the `train_steps` loop would leave
    it: another program's calls mixed in, the step program's first
    call, four more warm-up steps, a window of three, a traced stretch
    of two unfetched steps; a put a step, 1.5 ms long, the window's
    starting 1 ms before the step's `dispatched`."""
    from paddle_tpu.observability import step_record

    step_record.clear()
    recs, t, step = [], 1000 * MS, 110 * MS
    recs.append(_run(7, t - 50 * MS))                  # another program
    recs.append(_run(9, t, first_call=True, enqueue=6000))
    t += 6200 * MS
    for i in range(4):                                 # warm-up
        recs.append(_put(t + i * step, 9))
        recs.append(_run(9, t + i * step, prepare=9))
    recs.append(_run(7, t + 4 * step - 5 * MS))
    t += 4 * step
    for i, prep in enumerate((2, 4, 3)):               # the window
        recs.append(_run(9, t, prepare=prep, fetch=100 + i))
        # dispatched = enter + prep + 3 ms: 1 ms of the put inside
        recs.append(_put(t + (prep + 2) * MS, 1.5))
        t += step
    for i in range(2):                                 # traced stretch
        recs.append(_put(t, 20))
        recs.append(_run(9, t, fetched=False, prepare=20))
        t += step
    for r in recs:
        step_record._ring.append(r)
    yield step_record
    step_record.clear()


M = {"attempted": 3, "trace": None}


def test_window_is_the_last_attempted_fetched_steps(ring):
    assert _reader("run_prepare_ms").read(M) == pytest.approx(3.0)
    assert _reader("run_fetch_ms").read(M) == pytest.approx(101.0)
    # a window as long as everything fetched takes the warm-up in,
    # never the first call, the other program or the traced stretch
    assert _reader("run_prepare_ms").read(dict(M, attempted=7)) == \
        pytest.approx(9.0)         # 2 3 4 9 9 9 9
    assert _reader("run_prepare_ms").read(dict(M, attempted=99)) == \
        pytest.approx(9.0)
    assert _reader("run_prepare_ms").read(dict(M, attempted=4)) == \
        pytest.approx(3.5)         # 2 3 4 9


def test_put_readers_take_the_puts_inside_the_window(ring):
    assert _reader("feed_put_ms").read(M) == pytest.approx(1.5)
    assert _reader("feed_put_in_run_ms").read(M) == pytest.approx(1.0)
    # warm-up puts (9 ms, wholly inside their steps' prepare) join in
    # when the window takes the warm-up steps in
    assert _reader("feed_put_ms").read(dict(M, attempted=7)) == \
        pytest.approx(9.0)
    assert _reader("feed_put_in_run_ms").read(dict(M, attempted=7)) == \
        pytest.approx((4 * 9 + 3 * 1) / 7)


def test_first_call_is_the_step_programs(ring):
    assert _reader("first_call_s").read(M) == pytest.approx(6.0)


def test_a_raised_step_is_of_the_window_but_gives_no_time(ring):
    last = ring._ring[-1]["returned"]
    ring._ring.append({"kind": "run", "program": 9, "first_call": False,
                       "fetched": True, "thread": 1,
                       "enter": last + 10 * MS,
                       "feeds": last + 11 * MS})
    # the window: two whole steps (prepare 4, 3) and the one that raised
    assert _reader("run_prepare_ms").read(M) == pytest.approx(3.5)
    assert _reader("feed_put_in_run_ms").read(M) == pytest.approx(1.0)


@pytest.mark.parametrize("name", NEW[2:])
def test_empty_ring_reads_none(name):
    from paddle_tpu.observability import step_record

    step_record.clear()
    assert _reader(name).read(M) is None


@pytest.mark.parametrize("name", NEW[2:])
def test_program_without_the_record_reads_none(name, monkeypatch):
    """The parent commit has no step_record module: the import fails,
    and the reader must give None, not raise."""
    import sys

    import paddle_tpu.observability as obs

    monkeypatch.delattr(obs, "step_record")
    monkeypatch.setitem(sys.modules,
                        "paddle_tpu.observability.step_record", None)
    assert _reader(name).read(M) is None


# -- the trace's readers --------------------------------------------------------

def test_flash_readers_on_the_recorded_trace_from_before_the_names():
    """The dp2tp2 trace recorded by PR 22 calls its flash kernels
    mosaic:shard_map and the like: nothing to read."""
    r = tr.reduce(tr.read_xplane(
        os.path.join(DATA, "dp2tp2_3runs.xplane.pb.gz")))
    assert any(k.startswith("mosaic:")
               for k in r["devices"][r["first"]]["op_ns"])
    m = {"trace": r, "tr": tr}
    assert _reader("flash_fwd_ms").read(m) is None
    assert _reader("flash_bwd_ms").read(m) is None
    assert _reader("flash_ms").read(m) > 0
    assert _reader("flash_fwd_ms").read({"trace": None}) is None
    assert _reader("flash_bwd_ms").read({"trace": None}) is None


def test_flash_readers_sum_the_named_kernels():
    dev = {"steps": 4, "op_ns": {
        "mosaic:pt_flash_fwd": 8 * MS, "mosaic:pt_flash_bwd_dq": 6 * MS,
        "mosaic:pt_flash_bwd_dkv": 10 * MS, "fusion:fusion": 99 * MS},
        "category_ns": {"mosaic": 24 * MS}}
    # the first device is read, not the other
    m = {"trace": {"first": "d0", "devices": {
        "d0": dev, "d1": {"steps": 4, "op_ns": {}}}}, "tr": tr}
    fwd = _reader("flash_fwd_ms").read(m)
    bwd = _reader("flash_bwd_ms").read(m)
    assert (fwd, bwd) == (2.0, 4.0)
    assert fwd + bwd == _reader("flash_ms").read(m)
    del dev["op_ns"]["mosaic:pt_flash_bwd_dq"]
    assert _reader("flash_bwd_ms").read(m) == 2.5


def test_the_names_survive_the_reducers_grouping():
    for k in ("pt_flash_fwd", "pt_flash_bwd_dq", "pt_flash_bwd_dkv"):
        assert tr.group_name("%%%s.17 = bf16[4] custom-call()" % k,
                             "mosaic") == "mosaic:" + k


# -- BENCHMARK.json ---------------------------------------------------------------

def _spec():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_new_entry_resolves_to_a_reader():
    spec = _spec()
    new = [e for e in spec["per_layer"] if e["name"].split(".")[0] in NEW]
    assert len(new) == 11
    assert spec["per_layer"][-11:] == new          # appended, in order
    cells = {w["name"] for w in spec["workloads"]}
    rate = {"tokens_per_s": {"tfm_base_train_s512", "tfm_base_train_s8k",
                             "tfm_base_train_dp2tp2"},
            "images_per_s": {"rn50_train_b256"}}
    layers = {e["layer"] for e in spec["per_layer"][:-11]}
    for e in new:
        assert callable(_reader(e["name"].split(".")[0]).read)
        assert e["layer"] in layers
        assert e["source"] in ("device_trace", "program_counter")
        if e["moves"] == "setup_s":
            assert "workloads" not in e
        else:
            assert set(e["workloads"]) == rate[e["moves"]] <= cells
            assert e["name"].endswith(".img") == \
                (e["moves"] == "images_per_s")


# -- one tiny cell end to end on the CPU -------------------------------------------

def test_new_metrics_reach_the_result_line(tmp_path):
    """The real BENCHMARK.json's new entries, on a tiny Transformer cell
    through the harness: every step-record metric is on the --trace 1
    line with a finite value; the two device-trace ones are left out (a
    CPU trace has no device plane)."""
    import harness
    from test_rehearsal import CODE, JOBS, TINY_TFM

    bench = tmp_path / "benchmarks"
    bench.mkdir()
    for name in CODE:
        src = os.path.join(BENCH, name)
        (shutil.copytree if os.path.isdir(src) else shutil.copy)(
            src, bench / name)
    (bench / "peaks.json").write_text(json.dumps({
        "source": "made up for the CPU rehearsal",
        "kinds": {"cpu": {"bf16_flops_per_s": 1e12,
                          "hbm_bytes_per_s": 1e11}}}))
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    (bench / "configs" / "tiny-tfm.json").write_text(json.dumps(TINY_TFM))
    (bench / "traffic" / "tiny_seq.json").write_text(
        json.dumps(JOBS["tiny_seq"]))
    spec = _spec()
    per_layer = []
    for e in spec["per_layer"]:
        if e["name"].split(".")[0] in NEW + ["enqueue_ms", "step_p50_ms",
                                             "feed_wait_ms"] \
                and e["moves"] != "images_per_s":
            e = dict(e)
            if "workloads" in e:
                e["workloads"] = ["c_seq"]
            per_layer.append(e)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": spec["command"], "paths": ["benchmarks"],
        "run_seconds": 1,
        "configs": [{"name": "tiny-tfm", "source": "test", "reduced": [],
                     "file": "benchmarks/configs/tiny-tfm.json",
                     "why": "test"}],
        "workloads": [{"name": "c_seq", "config": "tiny-tfm",
                       "traffic": "tiny_seq", "chips": 4, "why": "test"}],
        "end_to_end": [e if "workloads" not in e
                       else dict(e, workloads=["c_seq"])
                       for e in spec["end_to_end"]
                       if e["name"] != "images_per_s"],
        "per_layer": per_layer}))
    out = io.StringIO()
    result = harness.run_cell(str(tmp_path), "c_seq", seed=2 ** 31 + 5,
                              seconds=0.5, trace=True, platform="cpu",
                              out=out)
    got = result["metrics"]
    assert result["correct"], out.getvalue()
    for name in NEW[2:]:
        assert math.isfinite(got[name]["value"]), name
        assert got[name]["value"] >= 0
    assert "flash_fwd_ms" not in got and "flash_bwd_ms" not in got
    assert got["first_call_s"]["value"] > got["run_prepare_ms"]["value"] / 1e3
