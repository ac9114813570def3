"""Paper §4.5.1 / Figures 8-10: data-parallel AtacWorks training at scale.

The paper scales AtacWorks training 1→16 CPU sockets with MPI and shows
per-socket throughput staying ~flat (near-linear scaling).  This benchmark
runs the mesh-native analogue for REAL — it executes the `shard_map` train
step (train/data_parallel.py, DESIGN.md §13) over data meshes of growing
device count and measures wall-clock throughput per count, emitting a
stable ``BENCH_scaling.json`` artifact (uploaded by CI next to the other
bench JSONs).

Two protocols, because "device" means different silicon in different runs:

  * ``--weak`` — the paper's protocol: per-device batch fixed, global
    batch grows with D.  Honest on real fleets (each device is its own
    silicon); ``efficiency`` is per-device throughput retention
    ``(tput(D)/D) / tput(1)``.
  * **fixed global batch** (default) — the honest protocol on ONE host
    faking D devices (``--xla_force_host_platform_device_count``), where
    all "devices" share the same cores and weak scaling would mostly
    measure oversubscription.  Total work is constant, so the metric
    isolates the *sharding tax* (program partitioning + the fused
    per-layer gradient all-reduces): ``efficiency = t(1)/t(D)`` — each
    device processes 1/D-th of the batch, and per-device throughput stays
    within the tax of the 1-device run.

A third axis (DESIGN.md §17): ``--layouts`` runs 2D ``(data, model)``
meshes — ``DPxMP`` cells — where the model axis K-shards every conv layer
(tensor parallelism).  Model-parallel rows additionally time the bwd-data
model psum both ways, single all-reduce vs chunked
(``model_reduce_chunks``), reporting the chunked step as the primary
``step_time_s`` next to ``model_psum_single_s`` and the speedup.  The
default smoke arch for layout runs is the paper's BF16 Cooper Lake
variant (``atacworks-bf16``, C=K=16) because the fp32 AtacWorks body
(C=K=15) does not divide over mp=2.

A fourth axis (DESIGN.md §18): ``--drill`` measures ELASTICITY instead of
steady-state scaling — it runs the real supervisor
(``repro.launch.train.run``) on 8 virtual devices with an injected fault
schedule and reports, per recovery: time-to-detect, time-to-restore, and
``post_shrink_efficiency`` (per-device throughput retention across the
dp-shrink at fixed global batch — can exceed 1 on an oversubscribed
virtual-device host, where fewer shards mean less contention; reported
as measured).  Drill rows land in the same ``BENCH_scaling.json`` under
``|drill|`` keys.

Virtual-device runs go to a CPU-only child process (``JAX_PLATFORMS=cpu``),
so their XLA_FLAGS never leak into the calling process and they never
contend for an accelerator the caller may hold; ``--no-force-host`` runs
in-process on the real devices.

    PYTHONPATH=src:. python benchmarks/bench_scaling.py --smoke
    PYTHONPATH=src:. python benchmarks/bench_scaling.py --devices 1,2,4,8 \
        --batch 16 --width 4096 --steps 5
    PYTHONPATH=src:. python benchmarks/bench_scaling.py --weak --batch 2
    PYTHONPATH=src:. python benchmarks/bench_scaling.py \
        --arch atacworks-bf16 --layouts 1x1,4x1,4x2,2x4 --batch 8
    PYTHONPATH=src:. python benchmarks/bench_scaling.py --smoke \
        --drill device_loss@5:4
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_child_env(n_devices: int) -> dict:
    """Environment of a virtual-device child: the CPU backend with
    ``n_devices`` host devices.  The children are CPU drills by design, so
    they never ask for (or wait on) an accelerator the parent may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), _ROOT, env.get("PYTHONPATH", "")])
    return env


def _run_child(src: str, arg: dict, n_devices: int) -> dict | list:
    """Run ``src`` (which prints one ``JSON:`` line) in a CPU child."""
    proc = subprocess.run([sys.executable, "-c", src, json.dumps(arg)],
                          env=_cpu_child_env(n_devices), capture_output=True,
                          text=True, timeout=3000)
    sys.stderr.write(proc.stderr[-2000:] if proc.returncode else "")
    for line in proc.stdout.splitlines():
        if line.startswith("#"):
            print(line)
        if line.startswith("JSON:"):
            return json.loads(line[5:])
    raise RuntimeError(f"child failed:\n{proc.stdout}\n{proc.stderr}")


_CHILD = ("import json, sys\n"
          "from benchmarks.bench_scaling import measure\n"
          "print('JSON:' + json.dumps(measure(json.loads(sys.argv[1]))))\n")

_DRILL_CHILD = ("import json, sys\n"
                "from repro.launch.train import run\n"
                "print('JSON:' + json.dumps(run(json.loads(sys.argv[1]))))\n")


def measure(args: dict) -> list[dict]:
    """Time the train step on each (dp, mp) layout of ``args['layouts']``
    over the devices of this process; returns one row per layout."""
    import jax

    from repro import configs
    from repro.data.synthetic import make_batch
    from repro.launch.mesh import make_data_mesh, make_grid_mesh
    from repro.models import get_model
    from repro.train.train_step import init_state, make_train_step
    from repro.tune.measure import median_time

    cfg = configs.get(args["arch"])
    model = get_model(cfg)
    params = model.init_params(jax.random.key(0), cfg)

    rows = []
    for dp, mp in args["layouts"]:
        d = dp * mp
        if mp > 1 and cfg.conv_channels % mp:
            raise SystemExit(
                f"layout {dp}x{mp}: conv_channels={cfg.conv_channels} does "
                "not divide over the model axis (pick a divisible arch, e.g. "
                "atacworks-bf16 with C=K=16; DESIGN.md §17)")
        # the batch shards over the data axis only (devices along 'model'
        # see the same shard), so --weak grows it with dp, not dp*mp
        gbatch = args["batch"] * (dp if args["weak"] else 1)
        mesh = make_data_mesh(dp) if mp == 1 else make_grid_mesh(dp, mp)
        # d == 1 exercises the plain single-program step (the baseline);
        # d > 1 the shard_map data/model-parallel path
        step = jax.jit(make_train_step(
            cfg, total_steps=100, mesh=mesh if d > 1 else None,
            model_reduce_chunks=args["model_chunks"] if mp > 1 else None))
        batch = make_batch(cfg, gbatch, args["width"], seed=0)
        state = init_state(params)
        sec = median_time(step, state, batch,
                          iters=args["iters"], warmup=args["warmup"])
        row = dict(devices=d, dp=dp, mp=mp, global_batch=gbatch,
                   local_batch=gbatch // dp, step_time_s=sec,
                   samples_per_s=gbatch / sec)
        note = ""
        if mp > 1:
            # the chunked-vs-single model-psum head-to-head: same layout,
            # bwd-data dx all-reduced in one piece instead of overlapped
            # width chunks (DESIGN.md §17)
            single = jax.jit(make_train_step(cfg, total_steps=100, mesh=mesh))
            sec1 = median_time(single, state, batch,
                               iters=args["iters"], warmup=args["warmup"])
            row["model_psum_single_s"] = sec1
            row["model_psum_chunks"] = args["model_chunks"]
            row["model_psum_chunked_speedup"] = sec1 / sec
            note = f" psum-chunk x{sec1 / sec:.2f}"
        rows.append(row)
        print(f"# dp={dp:2d} mp={mp} batch={gbatch:3d} "
              f"step={sec * 1e3:8.1f}ms {gbatch / sec:8.2f} samples/s{note}",
              flush=True)
    return rows


def run_drill(*, spec: str, arch: str = "atacworks", batch: int = 8,
              seq: int = 512, steps: int = 10, n_devices: int = 8):
    """Run the elastic supervisor with fault schedule ``spec`` on
    ``n_devices`` virtual devices; returns (drill rows, full summary)."""
    import tempfile

    with tempfile.TemporaryDirectory() as ckdir:
        summary = _run_child(_DRILL_CHILD, [
            "--arch", arch, "--smoke", "--steps", str(steps),
            "--batch", str(batch), "--seq", str(seq), "--ckpt-dir", ckdir,
            "--ckpt-every", "2", "--faults", spec], n_devices)
    rows = []
    for rec in summary["recoveries"]:
        rows.append(dict(
            kind=rec["kind"], fault_step=rec["fault_step"],
            restore_step=rec["restore_step"], dp_from=rec["dp_from"],
            dp_to=rec["dp_to"], mp=rec["mp"], accum=rec["accum"],
            time_to_detect_s=rec["time_to_detect_s"],
            time_to_restore_s=rec["time_to_restore_s"],
            pre_fault_step_s=rec.get("pre_fault_step_s"),
            post_recovery_step_s=rec.get("post_recovery_step_s"),
            post_shrink_efficiency=rec.get("post_shrink_efficiency")))
        print(f"# drill {rec['kind']}@{rec['fault_step']}: "
              f"dp {rec['dp_from']} -> {rec['dp_to']} "
              f"detect {rec['time_to_detect_s']:.3f}s "
              f"restore {rec['time_to_restore_s']:.3f}s "
              f"post-shrink eff {rec.get('post_shrink_efficiency', 0):.3f}",
              flush=True)
    return rows, summary


def run(*, arch: str, layouts: list[tuple[int, int]], batch: int, width: int,
        iters: int, warmup: int, weak: bool, force_host: bool = True,
        model_chunks: int = 2):
    child_args = dict(arch=arch, layouts=layouts, batch=batch, width=width,
                      iters=iters, warmup=warmup, weak=weak,
                      model_chunks=model_chunks)
    if force_host:
        rows = _run_child(_CHILD, child_args,
                          max(dp * mp for dp, mp in layouts))
    else:  # the real device set: this process owns it, no child
        rows = measure(child_args)
    # baseline = the smallest device count actually run (1 in the default
    # and smoke lists); efficiency is relative to ITS per-device numbers
    base = min(rows, key=lambda r: r["devices"])
    base_per_dev_tput = base["samples_per_s"] / base["devices"]
    for r in rows:
        if weak:
            # per-device throughput retention vs the baseline run
            r["efficiency"] = ((r["samples_per_s"] / r["devices"])
                               / base_per_dev_tput)
        else:
            # same total work: the sharding tax, t(base)/t(D)
            r["efficiency"] = base["step_time_s"] / r["step_time_s"]
        r["per_device_samples_per_s"] = r["samples_per_s"] / r["devices"]
        r["mode"] = "weak" if weak else "fixed-global-batch"
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", default=None,
                    help="model config (default atacworks; atacworks-bf16 "
                         "when --smoke/--layouts include a model axis — "
                         "C=K=15 does not divide over mp)")
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma list of data-parallel device counts")
    ap.add_argument("--layouts", default=None,
                    help="comma list of DPxMP mesh layouts (e.g. "
                         "'1x1,4x1,4x2'): overrides --devices and runs "
                         "each on a 2D (data, model) mesh — the model "
                         "axis K-shards the conv layers (DESIGN.md §17)")
    ap.add_argument("--model-chunks", type=int, default=2,
                    help="model_reduce_chunks for the chunked bwd-data "
                         "model psum on mp>1 layouts (the single-psum "
                         "baseline is always timed alongside)")
    ap.add_argument("--batch", type=int, default=8,
                    help="global batch (per-device batch with --weak)")
    ap.add_argument("--width", type=int, default=4096,
                    help="track segment width (paper: 60000)")
    ap.add_argument("--steps", "--iters", dest="iters", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--weak", action="store_true",
                    help="paper protocol: batch scales with devices "
                         "(meaningful on real multi-device hardware)")
    ap.add_argument("--no-force-host", action="store_true",
                    help="use the real device set instead of virtual "
                         "host devices")
    ap.add_argument("--smoke", action="store_true",
                    help="CI cell: dp-only layouts 1/2/8 plus the 4x2 "
                         "(data, model) grid, 8 virtual devices, small "
                         "width")
    ap.add_argument("--drill", nargs="?", const="device_loss@5:4",
                    default=None, metavar="SPEC",
                    help="also run an elastic-recovery drill (the real "
                         "supervisor with injected faults on 8 virtual "
                         "devices; runtime/faults.py grammar, default "
                         "'device_loss@5:4') and append time-to-detect/"
                         "time-to-restore/post-shrink-efficiency rows "
                         "(DESIGN.md §18)")
    ap.add_argument("--json", default="BENCH_scaling.json")
    args = ap.parse_args(argv)

    if args.layouts:
        layouts = []
        for cell in args.layouts.split(","):
            dp, _, mp = cell.lower().partition("x")
            layouts.append((int(dp), int(mp or 1)))
    else:
        layouts = [(int(d), 1) for d in args.devices.split(",")]
    batch, width, iters = args.batch, args.width, args.iters
    if args.smoke:
        layouts, batch, width, iters = [(1, 1), (2, 1), (8, 1), (4, 2)], 8, 2048, 3
    has_mp = any(mp > 1 for _, mp in layouts)
    # the fp32 AtacWorks body (C=K=15) cannot K-shard over mp=2; the
    # paper's BF16 variant (C=K=16) is the layout-grid default
    arch = args.arch or ("atacworks-bf16" if has_mp else "atacworks")

    rows = run(arch=arch, layouts=layouts, batch=batch, width=width,
               iters=iters, warmup=args.warmup, weak=args.weak,
               force_host=not args.no_force_host,
               model_chunks=args.model_chunks)

    cols = ["dp", "mp", "global_batch", "step_time_s", "samples_per_s",
            "per_device_samples_per_s", "efficiency"]
    print(",".join(cols))
    for r in rows:
        print(",".join(f"{r[c]:.4g}" if isinstance(r[c], float) else str(r[c])
                       for c in cols))

    from benchmarks.common import bench_entry, write_bench_json
    entries = {}
    for r in rows:
        # dp-only rows keep the historical dp{D} key so the cross-PR
        # trajectory stays comparable; 2D layouts get dp{D}xmp{M}
        layout = (f"dp{r['devices']}" if r["mp"] == 1
                  else f"dp{r['dp']}xmp{r['mp']}")
        extra = {}
        if r["mp"] > 1:
            extra = dict(model_psum_single_s=r["model_psum_single_s"],
                         model_psum_chunks=r["model_psum_chunks"],
                         model_psum_chunked_speedup=r[
                             "model_psum_chunked_speedup"])
        entries[f"{arch}|W{width}|B{r['global_batch']}|{layout}|"
                f"{r['mode']}"] = bench_entry(
            r["step_time_s"],
            samples_per_s=r["samples_per_s"],
            per_device_samples_per_s=r["per_device_samples_per_s"],
            efficiency=r["efficiency"],
            dp=r["dp"], mp=r["mp"],
            source="shard_map" if r["devices"] > 1 else "single-device",
            **extra)
    if args.drill:
        drows, dsummary = run_drill(spec=args.drill, batch=args.batch)
        for r in drows:
            key = (f"{dsummary['arch']}|drill|{r['kind']}@{r['fault_step']}|"
                   f"dp{r['dp_from']}->dp{r['dp_to']}")
            entries[key] = bench_entry(
                r["time_to_restore_s"],
                time_to_detect_s=r["time_to_detect_s"],
                pre_fault_step_s=r["pre_fault_step_s"],
                post_recovery_step_s=r["post_recovery_step_s"],
                post_shrink_efficiency=r["post_shrink_efficiency"],
                restore_step=r["restore_step"], mp=r["mp"],
                accum=r["accum"], source="elastic-drill")
        rows = rows + drows
    write_bench_json(args.json, entries)
    return rows


if __name__ == "__main__":
    main()
