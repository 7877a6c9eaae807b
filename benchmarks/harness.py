"""The benchmark's library entry: one cell, one process, one last line.

Driven by data.  This file holds no table of cells, configurations,
loop kinds or metrics; it finds each by the name BENCHMARK.json gives:

    BENCHMARK.json  workloads[name] -> config, traffic, chips
                    configs[config].file -> the configuration as run
    <bench>/traffic/<traffic>.json   job parameters; "kind" names
    <bench>/kinds/<kind>.py          the loop:  run(ctx) -> measurement
    configuration "builder"/"reference" name
    <bench>/builders/<builder>.py    build(config, job, flops)
    <bench>/reference/<reference>.py read_params, loss
    per_layer[name] -> <bench>/layer_metrics/<name up to the first '.'>.py
                                     read(measurement) -> number or None

where <bench> is the one entry of BENCHMARK.json's `paths`.  See
README.md for how a later PR adds any of these as files of its own.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time


class Refused(Exception):
    """The run cannot be made here (no chip, wrong chip count, an
    unknown cell): no result line, exit code other than 0."""


def _load_file(path):
    # never entered in sys.modules, so the name need not be unique
    spec = importlib.util.spec_from_file_location("_bm_file", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused("no %s named %r in BENCHMARK.json (known: %s)"
                  % (what, name, sorted(e["name"] for e in entries)))


def _in_cell(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def run_cell(checkout, workload, seed, seconds, trace, clock_start=None,
             platform="tpu", out=sys.stdout):
    """Runs one cell and prints its lines to `out`; the last is the
    result.  `platform` is what jax.devices()[0].platform has to be:
    "tpu" always, except in the CPU rehearsal test.  Returns the result
    as a dict."""
    clock_start = time.perf_counter() if clock_start is None \
        else clock_start
    spec = _read_json(os.path.join(checkout, "BENCHMARK.json"))
    bench = os.path.join(checkout, spec["paths"][0])
    cell = _by_name(spec["workloads"], workload, "workload")
    cfg_entry = _by_name(spec["configs"], cell["config"], "config")
    config = _read_json(os.path.join(checkout, cfg_entry["file"]))
    job = _read_json(os.path.join(bench, "traffic",
                                  cell["traffic"] + ".json"))

    # the persistent compile cache: where the environment says, else a
    # fixed path inside the benchmark's directory.  The program's
    # enable_compile_cache() takes the environment's directory.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(bench, ".jax_cache"))
    if checkout not in sys.path:
        sys.path.insert(0, checkout)
    import jax

    import paddle_tpu

    devices = jax.devices()
    if devices[0].platform != platform:
        raise Refused("no accelerator: jax.devices()[0] is %s (%s); the "
                      "benchmark runs on the %s or not at all"
                      % (devices[0].platform, devices[0].device_kind,
                         platform))
    if len(devices) != cell["chips"]:
        raise Refused("cell %s needs %d chip(s), jax sees %d"
                      % (workload, cell["chips"], len(devices)))
    flops = _load_file(os.path.join(bench, "flops.py"))
    peaks = flops.load_peaks(os.path.join(bench, "peaks.json"),
                             devices[0].device_kind)
    cache_dir = paddle_tpu.enable_compile_cache()

    def say(**fields):
        print(json.dumps(fields, default=str), file=out, flush=True)

    def load(kind, name):
        return _load_file(os.path.join(bench, kind, name + ".py"))

    say(event="start", workload=workload, seed=seed, seconds=seconds,
        trace=int(trace), cache_dir=cache_dir, jax=jax.__version__,
        device_kind=devices[0].device_kind, chips=len(devices))
    reducer = _load_file(os.path.join(bench, "trace_reduce.py"))
    m = load("kinds", job["kind"]).run({
        "config": config, "job": job, "seed": seed, "seconds": seconds,
        "trace": bool(trace), "clock_start": clock_start,
        "devices": devices, "load": load, "flops": flops, "say": say,
        "observe": _load_file(os.path.join(bench, "observe.py")),
        "trace_reduce": reducer,
        "scratch_dir": os.path.join(bench, "out", "_trace_" + workload),
    })
    # what a per-layer reader may use besides the measurement itself
    m.update(peaks=peaks, chips=len(devices), config=config, job=job,
             flops=flops, tr=reducer)

    # -- the metrics this run reports, by BENCHMARK.json ------------------
    metrics = {}
    if not trace:
        have = m["end_to_end"]
        for e in spec["end_to_end"]:
            if _in_cell(e, workload):
                if e["name"] not in have:
                    raise Refused("cell %s does not produce the "
                                  "end-to-end metric %s"
                                  % (workload, e["name"]))
                metrics[e["name"]] = {"value": have[e["name"]],
                                      "unit": e["unit"]}
    else:
        for e in spec["per_layer"]:
            if not _in_cell(e, workload):
                continue
            reader = load("layer_metrics", e["name"].split(".")[0])
            value = reader.read(m)
            if value is not None:
                metrics[e["name"]] = {"value": float(value),
                                      "unit": e["unit"]}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": _memory_peak(devices, m)}
    result = {"correct": bool(m["correct"]), "attempted": m["attempted"],
              "failed": m["failed"], "metrics": metrics, "device": device}
    reduced = m["trace"]
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        # idle time shared out among host phases, then single gaps
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": ([["all:" + k, v]
                           for k, v in reduced["idle_by_phase"]]
                          + [["longest:" + k, v]
                             for k, v in reduced["idle_gaps"]])[:10]}
    elif trace and platform == "tpu":
        raise RuntimeError("the traced stretch held no device operation")
    _write_record(bench, workload, seed, trace, m, result)
    say(**result)
    return result


def _memory_peak(devices, m):
    """Peak bytes on the fullest chip: the larger of what the allocator
    reports (memory_stats) and what the compiled step holds by its own
    memory_analysis, because the allocator's peak was seen not to count
    XLA's temporaries (PERF.md section 7)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
             for d in devices]
    return int(max(peaks + [m["memory"]["step_bytes"]]))


def _write_record(bench, workload, seed, trace, m, result):
    """What the loop kind keeps of the run (for train_steps the losses
    at fixed step indices, so that a later PR can show that the same
    seed gives the same losses, and every step's times) and the
    result."""
    out_dir = os.path.join(bench, "out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "%s.seed%d.trace%d.json"
                        % (workload, seed, int(trace)))
    with open(path, "w") as f:
        json.dump(dict(m["record"], result=result), f, indent=1)
