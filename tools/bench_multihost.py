"""Multi-host benchmark harness (round-4 verdict missing #3; reference
cluster bench driver: tools/aws_benchmarking/README.md:1 +
server/cluster_master.py, and the per-host env contract of
python/paddle/distributed/launch.py:132).

Two modes, selected by the presence of the launch env contract:

* driver (no PADDLE_TRAINER_ID): spawns --nnodes worker processes on
  this machine, each styled as one "host" of the cluster with the
  exact PADDLE_* env `paddle_tpu.launch` injects (distinct ports since
  every simulated host shares 127.0.0.1), each seeing
  --devices-per-host virtual CPU devices.  Collects every host's
  RESULT line and prints ONE JSON summary with global + per-host
  throughput.  On a real cluster run the WORKER on every host instead:
      python -m paddle_tpu.launch --nnodes N --node_rank R \
          --node_ips ip0,ip1,... tools/bench_multihost.py
* worker (PADDLE_TRAINER_ID set): fleet.init() wires jax.distributed
  from the env, every host contributes its local devices to one global
  dp mesh, feeds enter per-host via
  jax.make_array_from_process_local_data, and the timed step is a
  jitted fwd+bwd+SGD whose gradient psum rides the XLA collectives —
  the comm backend SURVEY §5 mandates.

--mode gspmd (ISSUE 8): the IR transformer train step through
transpiler.shard_program instead of the raw-jax leg — ONE pjit
program over the global dp x tp mesh with ZeRO-3/tp PartitionSpec
annotations, per-host feeds globalized by CompiledProgram, and
per-host + global MFU in the one-JSON-line summary.
``--simulate-hosts N`` runs the identical sharded step single-process
over the virtual mesh partitioned into N device groups
(dryrun_multichip style — what tools/ci.sh smokes; the spawn path is
for real jax.distributed fleets, which this container's CPU backend
cannot execute: "Multiprocess computations aren't implemented").

Doc: docs/MULTIHOST.md, docs/GSPMD.md.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _parse(argv=None):
    p = argparse.ArgumentParser("bench_multihost")
    p.add_argument("--nnodes", type=int, default=2,
                   help="driver: simulated hosts to spawn")
    p.add_argument("--devices-per-host", type=int, default=4)
    p.add_argument("--batch-per-host", type=int, default=256)
    p.add_argument("--dim", type=int, default=512)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--mode", choices=["dp", "gspmd"], default="dp",
                   help="dp: the raw-jax data-parallel leg; gspmd: the "
                        "ISSUE-8 IR transformer step as ONE pjit "
                        "program over the global dp x tp mesh "
                        "(transpiler.shard_program), per-host + "
                        "global MFU in the summary line")
    p.add_argument("--tp", type=int, default=2,
                   help="gspmd: tensor-parallel axis size (clamped to "
                        "the global device count)")
    p.add_argument("--seq", type=int, default=32,
                   help="gspmd: sequence length of the smoke "
                        "transformer")
    p.add_argument("--simulate-hosts", type=int, default=0,
                   help="gspmd: run N simulated hosts in ONE process "
                        "over the virtual device mesh "
                        "(dryrun_multichip style — the ci.sh smoke; "
                        "per-host rows are device-group attributions "
                        "of the one timed run).  Use the driver/worker "
                        "spawn path for real jax.distributed hosts.")
    return p.parse_args(argv)


# --------------------------------------------------------------------------
# gspmd leg (ISSUE 8): the IR transformer train step through
# transpiler.shard_program — one jit with in/out NamedShardings over
# the GLOBAL mesh; ZeRO-3 + tp as PartitionSpec annotations.
# --------------------------------------------------------------------------

# smoke transformer (small on purpose: the leg proves the multi-host
# gspmd path — mesh spanning hosts, per-host feeds, sharded state
# commit — not kernel throughput; a real MFU comes from the chip,
# the benchmark's cell tfm_base_train_dp2tp2)
GSPMD_SMOKE = dict(vocab=512, d_model=64, n_head=4, d_inner=128,
                   n_layer=2)


def _gspmd_build(global_batch, seq, tp):
    """Build + shard the smoke transformer over ALL global devices;
    returns (exe, compiled, loss_name, plan, flops_per_token)."""
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import framework, optimizer
    from paddle_tpu.flags import set_flags
    from paddle_tpu.models.transformer import transformer_encoder_model
    from paddle_tpu.parallel.gspmd import MeshPlan
    from paddle_tpu.transpiler import shard_program

    set_flags({"gspmd": True})
    c = GSPMD_SMOKE
    model = transformer_encoder_model(
        vocab_size=c["vocab"], max_len=seq, d_model=c["d_model"],
        n_head=c["n_head"], d_inner=c["d_inner"], n_layer=c["n_layer"],
        dropout_rate=0.0, param_prefix="tfm")
    optimizer.Adam(1e-3).minimize(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    np.random.seed(0)  # identical startup state on every host
    exe.run(framework.default_startup_program())
    ndev = len(jax.devices())
    tp_eff = max(1, min(int(tp), ndev))
    while ndev % tp_eff != 0:
        tp_eff -= 1
    plan = MeshPlan(dp=ndev // tp_eff, tp=tp_eff)
    compiled = shard_program(
        fluid.CompiledProgram(framework.default_main_program()),
        plan, loss_name=model["loss"].name, min_size=1024)
    n_params = (c["vocab"] * c["d_model"] + seq * c["d_model"]
                + c["n_layer"] * (4 * c["d_model"] ** 2
                                  + 2 * c["d_model"] * c["d_inner"])
                + c["d_model"] * c["vocab"])
    fpt = 6.0 * n_params + 12.0 * c["n_layer"] * c["d_model"] * seq
    return exe, compiled, model["loss"].name, plan, fpt


def _cpu_peak_flops():
    """Nominal per-'chip' peak for MFU on the simulated mesh — an
    arbitrary 100 GFLOP/s anchor; a real MFU comes from the chip
    (benchmarks/run.py, PERF_LEDGER.jsonl)."""
    import jax

    dev = jax.devices()[0]
    kind = str(getattr(dev, "device_kind", dev.platform))
    if "v5p" in kind:
        return 459e12, kind
    if "v5" in kind or "v5e" in kind:
        return 197e12, kind
    if "v4" in kind:
        return 275e12, kind
    return 1e11, kind


def gspmd_worker(args):
    """One jax.distributed host of the gspmd leg: every host
    contributes its devices to ONE global dp x tp mesh, feeds enter
    per-host (CompiledProgram._globalize shards them over dp and
    commits ZeRO-3/tp state per annotation), the timed step is the one
    pjit program.  Prints the per-host RESULT line."""
    import jax

    if os.environ.get("PADDLE_TPU_PLATFORM", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from paddle_tpu.fleet import fleet
    from paddle_tpu.fleet.role_maker import PaddleCloudRoleMaker

    fleet.init(PaddleCloudRoleMaker())
    rank = jax.process_index()
    nproc = jax.process_count()
    global_batch = args.batch_per_host * nproc
    exe, compiled, loss_name, plan, fpt = _gspmd_build(
        global_batch, args.seq, args.tp)
    rng = np.random.RandomState(0)  # step-keyed identical global data
    ids = rng.randint(0, GSPMD_SMOKE["vocab"],
                      (global_batch, args.seq, 1)).astype(np.int64)
    # each host feeds its LOCAL rows; _globalize assembles the global
    # dp-sharded array from the per-process shards
    local = ids[rank * args.batch_per_host:
                (rank + 1) * args.batch_per_host]
    feed = {"src_ids": local, "tgt_label": local}
    for _ in range(args.warmup):
        loss, = exe.run(compiled, feed=feed, fetch_list=[loss_name])
    t0 = time.perf_counter()
    for _ in range(args.steps):
        loss, = exe.run(compiled, feed=feed, fetch_list=[loss_name])
    dt = time.perf_counter() - t0
    toks = global_batch * args.seq * args.steps / dt
    host_toks = args.batch_per_host * args.seq * args.steps / dt
    peak, kind = _cpu_peak_flops()
    out = {
        "host": rank,
        "hosts": nproc,
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
        "endpoint": os.environ.get("PADDLE_CURRENT_ENDPOINT"),
        "steps": args.steps,
        "step_ms": round(dt / args.steps * 1e3, 3),
        "tokens_per_sec": round(toks, 1),
        "host_tokens_per_sec": round(host_toks, 1),
        "mfu_pct": round(
            100 * fpt * toks / (peak * len(jax.devices())), 4),
        "host_mfu_pct": round(
            100 * fpt * host_toks / (peak * len(jax.local_devices())),
            4),
        "dp": plan.axes["dp"],
        "tp": plan.axes["tp"],
        "device": kind,
        "loss": float(np.asarray(loss)),
    }
    print("RESULT " + json.dumps(out), flush=True)
    return 0


def gspmd_simulated(args):
    """dryrun_multichip-style smoke: ONE process, the virtual
    multi-device mesh partitioned into --simulate-hosts device groups.
    Runs the identical sharded step a real multi-host fleet jits and
    prints the same one-JSON-line summary (per-host rows are
    device-group attributions of the one timed run — honest about
    being simulated via "simulated_hosts")."""
    want = args.devices_per_host * args.simulate_hosts
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % want
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    nhosts = args.simulate_hosts
    ndev = len(jax.devices())
    if ndev % nhosts != 0:
        print(json.dumps({"error": "simulate-hosts %d does not divide "
                                   "%d devices" % (nhosts, ndev)}))
        return 1
    global_batch = args.batch_per_host * nhosts
    exe, compiled, loss_name, plan, fpt = _gspmd_build(
        global_batch, args.seq, args.tp)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, GSPMD_SMOKE["vocab"],
                      (global_batch, args.seq, 1)).astype(np.int64)
    feed = {"src_ids": ids, "tgt_label": ids}
    for _ in range(args.warmup):
        loss, = exe.run(compiled, feed=feed, fetch_list=[loss_name])
    t0 = time.perf_counter()
    for _ in range(args.steps):
        loss, = exe.run(compiled, feed=feed, fetch_list=[loss_name])
    dt = time.perf_counter() - t0
    toks = global_batch * args.seq * args.steps / dt
    peak, kind = _cpu_peak_flops()
    mfu = 100 * fpt * toks / (peak * ndev)
    dper = ndev // nhosts
    per_host = [{
        "host": h,
        "local_devices": dper,
        "step_ms": round(dt / args.steps * 1e3, 3),
        "host_tokens_per_sec": round(toks / nhosts, 1),
        "host_mfu_pct": round(mfu, 4),
    } for h in range(nhosts)]
    print(json.dumps({
        "metric": "multihost_gspmd_train",
        "value": round(mfu, 4),
        "unit": "% of fleet peak",
        "simulated_hosts": True,
        "hosts": nhosts,
        "devices_per_host": dper,
        "global_devices": ndev,
        "global_batch": global_batch,
        "seq": args.seq,
        "dp": plan.axes["dp"],
        "tp": plan.axes["tp"],
        "tokens_per_sec": round(toks, 1),
        "step_ms": round(dt / args.steps * 1e3, 3),
        "mfu_pct": round(mfu, 4),
        "device": kind,
        "loss": float(np.asarray(loss)),
        "per_host": per_host,
    }))
    return 0


# --------------------------------------------------------------------------
# worker
# --------------------------------------------------------------------------

def worker(args):
    import jax

    if os.environ.get("PADDLE_TPU_PLATFORM", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.fleet import fleet
    from paddle_tpu.fleet.role_maker import PaddleCloudRoleMaker

    fleet.init(PaddleCloudRoleMaker())
    rank = jax.process_index()
    nproc = jax.process_count()
    devs = np.asarray(jax.devices())
    mesh = Mesh(devs, ("dp",))
    repl = NamedSharding(mesh, P())
    dp = NamedSharding(mesh, P("dp"))

    d = args.dim
    rng = np.random.RandomState(0)
    w1 = jax.device_put(rng.randn(d, d).astype(np.float32) * 0.05, repl)
    w2 = jax.device_put(rng.randn(d, 1).astype(np.float32) * 0.05, repl)
    lrng = np.random.RandomState(100 + rank)
    xl = lrng.rand(args.batch_per_host, d).astype(np.float32)
    yl = np.tanh(xl.sum(axis=1, keepdims=True) * 0.1).astype(np.float32)
    # per-host shards -> one global [nproc*batch_per_host, d] array
    xg = jax.make_array_from_process_local_data(dp, xl)
    yg = jax.make_array_from_process_local_data(dp, yl)

    @jax.jit
    def step(w1, w2, x, y):
        def loss_fn(w1, w2):
            h = jnp.tanh(x @ w1)
            return jnp.mean((h @ w2 - y) ** 2)

        l, g = jax.value_and_grad(loss_fn, argnums=(0, 1))(w1, w2)
        return w1 - 0.05 * g[0], w2 - 0.05 * g[1], l

    for _ in range(args.warmup):
        w1, w2, loss = step(w1, w2, xg, yg)
    jax.block_until_ready((w1, w2))
    t0 = time.perf_counter()
    for _ in range(args.steps):
        w1, w2, loss = step(w1, w2, xg, yg)
    jax.block_until_ready((w1, w2))
    dt = time.perf_counter() - t0

    global_batch = args.batch_per_host * nproc
    out = {
        "host": rank,
        "hosts": nproc,
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
        "endpoint": os.environ.get("PADDLE_CURRENT_ENDPOINT"),
        "steps": args.steps,
        "step_ms": round(dt / args.steps * 1e3, 3),
        "examples_per_sec": round(global_batch * args.steps / dt, 1),
        "host_examples_per_sec": round(
            args.batch_per_host * args.steps / dt, 1),
        "loss": float(loss),
    }
    print("RESULT " + json.dumps(out), flush=True)
    return 0


# --------------------------------------------------------------------------
# driver: a local cluster through the launch.py env contract
# --------------------------------------------------------------------------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def driver(args):
    """One process per simulated host, each FORCED onto the CPU's
    virtual devices (PADDLE_TPU_PLATFORM=cpu below): a chip belongs to
    one process, so N "hosts" on one machine would otherwise fight over
    its chips.  This parent never touches JAX.  On a real cluster run
    the worker on every host instead (module docstring)."""
    eps = [f"127.0.0.1:{_free_port()}" for _ in range(args.nnodes)]
    procs = []
    for rank in range(args.nnodes):
        env = {
            **os.environ,
            # the paddle_tpu.launch contract (launch.py:55); distinct
            # ports because every simulated host shares one ip
            "PADDLE_TRAINING_ROLE": "TRAINER",
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(args.nnodes),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(eps),
            "PADDLE_CURRENT_ENDPOINT": eps[rank],
            "PADDLE_COORDINATOR_ENDPOINT": eps[0],
            "PADDLE_TPU_PLATFORM": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count="
                         f"{args.devices_per_host}",
            "PYTHONPATH": REPO + os.pathsep +
                          os.environ.get("PYTHONPATH", ""),
        }
        cmd = [sys.executable, os.path.abspath(__file__),
               "--batch-per-host", str(args.batch_per_host),
               "--dim", str(args.dim), "--steps", str(args.steps),
               "--warmup", str(args.warmup), "--mode", args.mode,
               "--tp", str(args.tp), "--seq", str(args.seq)]
        procs.append(subprocess.Popen(
            cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    results, errs = [], []
    for pr in procs:
        try:
            out, err = pr.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            pr.kill()
            out, err = pr.communicate()
        if pr.returncode != 0:
            errs.append(err[-500:])
        for ln in out.splitlines():
            if ln.startswith("RESULT "):
                results.append(json.loads(ln[len("RESULT "):]))
    if len(results) != args.nnodes:
        print(json.dumps({"error": "hosts failed",
                          "got": len(results),
                          "stderr": errs}))
        return 1
    results.sort(key=lambda r: r["host"])
    if args.mode == "gspmd":
        # the slowest host bounds the synchronized pjit step; global
        # MFU is the fleet row, per-host MFU names a straggler
        mfu = min(r["mfu_pct"] for r in results)
        summary = {
            "metric": "multihost_gspmd_train",
            "value": mfu,
            "unit": "% of fleet peak",
            "simulated_hosts": False,
            "hosts": args.nnodes,
            "devices_per_host": args.devices_per_host,
            "global_devices": results[0]["global_devices"],
            "global_batch": args.batch_per_host * args.nnodes,
            "seq": args.seq,
            "dp": results[0]["dp"],
            "tp": results[0]["tp"],
            "tokens_per_sec": min(r["tokens_per_sec"]
                                  for r in results),
            "step_ms": max(r["step_ms"] for r in results),
            "mfu_pct": mfu,
            "device": results[0]["device"],
            "loss": results[0]["loss"],
            "per_host": [
                {k: r[k] for k in ("host", "endpoint", "step_ms",
                                   "host_tokens_per_sec",
                                   "host_mfu_pct", "local_devices")}
                for r in results
            ],
        }
    else:
        summary = {
            "metric": "multihost_dp_train",
            "hosts": args.nnodes,
            "devices_per_host": args.devices_per_host,
            "global_batch": args.batch_per_host * args.nnodes,
            # the slowest host bounds the synchronized step
            "examples_per_sec": min(r["examples_per_sec"]
                                    for r in results),
            "step_ms": max(r["step_ms"] for r in results),
            "per_host": [
                {k: r[k] for k in ("host", "endpoint", "step_ms",
                                   "host_examples_per_sec",
                                   "local_devices")}
                for r in results
            ],
        }
    print(json.dumps(summary))
    return 0


def main(argv=None):
    args = _parse(argv)
    if args.mode == "gspmd" and args.simulate_hosts > 0:
        return gspmd_simulated(args)
    if os.environ.get("PADDLE_TRAINER_ID") is not None:
        return gspmd_worker(args) if args.mode == "gspmd" \
            else worker(args)
    return driver(args)


if __name__ == "__main__":
    sys.exit(main())
