"""Training launcher — the end-to-end driver (deliverable (b)).

Runs REAL steps on whatever devices exist (CPU here; the same code path
lowers against the production mesh in dryrun.py).  Wires together every
substrate layer: config registry, synthetic data pipeline with host
prefetch, sharded train step with grad accumulation, checkpoint/restore
(async, atomic, elastic), NaN-guard + health monitor, straggler detector,
and preemption-flush.

Data parallelism (DESIGN.md §13): for the conv family on a multi-device
data mesh, the step runs through the explicit ``shard_map`` path
(``train/data_parallel.py``) — per-shard local-shape tracing (so tuner
plans resolve from local ``ConvProblem`` keys) with the weight-gradient
all-reduces fused into the conv custom VJPs.  Other families keep the
GSPMD path (FSDP-sharded params via ``models/sharding.py``).  To exercise
the sharded path on a CPU-only host, give jax virtual devices BEFORE the
process starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.train --arch atacworks \
        --smoke --steps 8 --batch 8 --seq 2048

Elastic fault tolerance (DESIGN.md §18): the launcher is a *supervisor*
over mesh generations.  Each step it consumes the ``HealthMonitor``, the
``ShardStragglerMonitor``, the ``PreemptionGuard``, and — in drills — a
``runtime.faults.FaultInjector``.  On a device loss (or a straggler the
monitor votes to REPLACE) it re-plans the mesh over the survivors with
``runtime.elastic.make_plan`` (model axis fixed, data axis shrunk,
grad-accumulation re-derived so the GLOBAL batch is preserved exactly),
restores from the mesh-agnostic checkpoint, rebuilds the jitted step
against the new mesh, and resumes — batches are step-keyed, so the
replayed steps see the data they saw the first time.  Recovery is
observable (``elastic.fault`` / ``elastic.detect`` / ``elastic.recover``
telemetry, gated in CI by ``obs_report.py --check-elastic``):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.train --arch atacworks \
        --smoke --steps 10 --batch 8 --seq 512 --ckpt-dir /tmp/ck \
        --ckpt-every 2 --faults device_loss@5:4

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch atacworks --smoke \
        --steps 20 --batch 4 --seq 4096
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
        --steps 10 --batch 8 --seq 128 --accum 2 --ckpt-dir /tmp/ck --ckpt-every 5
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs, obs
from repro.checkpoint.checkpoint import Checkpointer
from repro.configs.base import reduced
from repro.data.synthetic import SyntheticLoader
from repro.launch.mesh import (compat_make_mesh, dp_size, make_elastic_mesh,
                               mp_size)
from repro.models import get_model, sharding as shd
from repro.runtime.elastic import make_plan, plan_mesh
from repro.runtime.faults import FaultInjector, parse_faults
from repro.runtime.health import HealthMonitor, PreemptionGuard
from repro.runtime.straggler import ShardStragglerMonitor
from repro.train.train_step import init_state, make_phase_probes, \
    make_train_step

# steps excluded from throughput: step 0 pays compile, step 1 still hits
# first-touch allocator costs — both would poison a samples/s claim
WARMUP_STEPS = 2


def _parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8,
                    help="GLOBAL batch — the elastic invariant: preserved "
                         "exactly across every mesh re-plan")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="tensor-parallel (model-axis) size: K-shards the "
                         "conv filters over a (data, model) mesh planned "
                         "by runtime.elastic.plan_mesh (DESIGN.md §17); "
                         "requires n_devices %% N == 0 and "
                         "conv_channels %% N == 0.  The model axis NEVER "
                         "changes across elastic re-plans")
    ap.add_argument("--model-reduce-chunks", type=int, default=None,
                    help="with --model-parallel > 1: chunk each layer's "
                         "bwd-data model-axis psum into this many width "
                         "chunks so the all-reduce overlaps the remaining "
                         "contraction (DESIGN.md §17)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-shard-map", action="store_true",
                    help="force the GSPMD path even for conv on a "
                         "multi-device data mesh")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="fault-injection drill schedule "
                         "(runtime/faults.py grammar, e.g. "
                         "'device_loss@5:4', 'straggle@6:1x4', "
                         "'preempt@8'); device_loss/straggle recovery "
                         "restores from --ckpt-dir")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write a telemetry JSONL log to PATH (same as "
                         "REPRO_TELEMETRY=1 + REPRO_TELEMETRY_PATH)")
    return ap.parse_args(argv)


def _build_state(model, cfg, mesh, seed):
    """Init params against the CURRENT mesh's shardings — also the restore
    template: the checkpoint stores mesh-agnostic whole arrays, placement
    happens against whatever this mesh prescribes."""
    params = model.init_params(jax.random.key(seed), cfg)
    psh = jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s),
                       shd.param_pspecs(params, mesh))
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    state = init_state(jax.device_put(params, psh))
    # the optimizer moments sit like their params and the counters
    # replicated, as the step returns them: step 0's inputs then have the
    # shardings of every later step's, and the step compiles once
    return jax.device_put(state, state._replace(
        params=psh, opt=state.opt._replace(m=psh, v=psh, count=rep),
        step=rep))


def run(argv=None) -> dict:
    """The supervisor: runs the training loop across mesh generations and
    returns a JSON-safe summary (losses, recoveries, per-generation step
    times) — the drill benchmark and the chaos tests consume this."""
    args = _parse_args(argv)
    if args.telemetry:
        obs.enable(args.telemetry)

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    n_dev = len(jax.devices())
    if args.model_parallel < 1 or n_dev % args.model_parallel:
        raise SystemExit(
            f"--model-parallel {args.model_parallel} does not divide the "
            f"{n_dev} available device(s); runtime.elastic.plan_mesh only "
            "plans whole (data, model) rows — pick a model-axis size with "
            "n_devices % N == 0")
    shape0, axis_names0 = plan_mesh(n_dev, model_parallel=args.model_parallel)
    dp0 = int(np.prod([s for s, a in zip(shape0, axis_names0)
                       if a in ("pod", "data")]))
    mp = args.model_parallel
    if mp > 1:
        # the model axis shards filter/channel dims, not the batch — its
        # divisibility constraints are the model's, not the loader's
        if cfg.family != "conv":
            raise SystemExit(
                f"--model-parallel needs the conv family (arch {cfg.name} "
                f"is family {cfg.family!r}): only the conv layers K-shard "
                "over the model axis; other families shard via GSPMD "
                "rules without this flag")
        if args.no_shard_map:
            raise SystemExit(
                "--model-parallel requires the explicit shard_map path; "
                "drop --no-shard-map")
        C = cfg.conv_channels
        if C % mp:
            raise SystemExit(
                f"--model-parallel {mp} does not divide this model's "
                f"filter/channel counts: conv_channels={C} (every body "
                f"layer has K=C={C} filters and depthwise channel groups "
                "split on C), so C % mp must be 0 — use an arch/smoke "
                "config with divisible channels or lower --model-parallel "
                "(DESIGN.md §17)")
    if args.batch % args.accum:
        raise SystemExit(f"--batch {args.batch} must divide by --accum "
                         f"{args.accum}")
    # conv family + a multi-device data or model axis -> the explicit
    # shard_map path; each microbatch must split evenly over the data shards
    shard_path = (cfg.family == "conv" and (dp0 > 1 or mp > 1)
                  and not args.no_shard_map)
    if shard_path and (args.batch // args.accum) % dp0:
        raise SystemExit(
            f"microbatch {args.batch // args.accum} must divide over "
            f"dp={dp0} shards (see runtime.elastic.plan_batch for a legal "
            "(accum, microbatch) split)")

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    injector = None
    if args.faults:
        faults = parse_faults(args.faults)
        if any(f.kind in ("device_loss", "straggle") for f in faults) \
                and not ckpt:
            raise SystemExit(
                "--faults with device_loss/straggle needs --ckpt-dir: "
                "recovery restores from the last committed checkpoint "
                "(the in-memory state lives on the lost devices)")
        injector = FaultInjector(faults, jax.devices())
    # the per-shard microbatch the launch layout implies — what every
    # elastic re-plan holds fixed (plan_batch's max_microbatch_per_shard)
    # so accum * microbatch always reproduces the global batch exactly
    micro_cap = max(1, (args.batch // args.accum) // dp0)

    model = get_model(cfg)
    health = HealthMonitor()
    # drills feed the monitor per-shard clean/slow times with compile steps
    # excluded, so the detector warmup only needs to cover steady noise;
    # production runs keep the conservative default
    straggler = (ShardStragglerMonitor(warmup=WARMUP_STEPS) if args.faults
                 else ShardStragglerMonitor())
    guard = PreemptionGuard()
    pid = int(jax.process_index())

    losses: dict[int, float] = {}
    dts: dict[int, float] = {}
    recoveries: list[dict] = []
    mesh_history: list[dict] = []
    pending = None          # recovery in flight (set when a fault breaks out)
    start_step = 0
    status = "done"
    state = None

    try:
        while True:
            healthy = ([d for d in jax.devices()
                        if d.id in set(injector.healthy())]
                       if injector else list(jax.devices()))
            if len(healthy) < mp:
                raise SystemExit(
                    f"only {len(healthy)} healthy device(s) left; the "
                    f"model axis needs {mp} — cannot re-plan (the model "
                    "axis never changes across elastic re-plans)")
            gen = len(mesh_history)
            if gen == 0:
                # launch layout: all devices, the user's accum
                mesh = compat_make_mesh(shape0, axis_names0)
                accum = args.accum
            else:
                # re-plan over the survivors: model axis fixed, data axis
                # shrunk to the largest batch-divisible row count,
                # accumulation re-derived -> same GLOBAL batch, same
                # training trajectory
                plan = make_plan(len(healthy), model_parallel=mp,
                                 global_batch=args.batch,
                                 max_microbatch_per_shard=micro_cap)
                mesh = make_elastic_mesh(plan.mesh_shape, plan.axis_names,
                                         healthy)
                accum = plan.accum_steps
            dp = dp_size(mesh)
            shard_step = (cfg.family == "conv" and (dp > 1 or mp > 1)
                          and not args.no_shard_map)
            print(f"arch={cfg.name} "
                  f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
                  f"batch={args.batch} accum={accum} "
                  f"path={'shard_map' if shard_step else 'gspmd'}")

            step_fn = make_train_step(
                cfg, accum_steps=accum, peak_lr=args.lr,
                warmup_steps=max(2, args.steps // 10),
                total_steps=args.steps,
                mesh=mesh if shard_step else None,
                model_reduce_chunks=args.model_reduce_chunks
                if shard_step and mp > 1 else None)

            with mesh:
                state = _build_state(model, cfg, mesh, args.seed)
                if gen == 0:
                    if ckpt and args.resume and ckpt.latest_step() is not None:
                        state = ckpt.restore(state)
                        start_step = int(state.step)
                        print(f"resumed from step {start_step}")
                    if injector and ckpt and ckpt.latest_step() is None:
                        # bootstrap restore point: a fault before the first
                        # periodic save must still have somewhere to go
                        ckpt.save(state, start_step)
                else:
                    ckpt.wait()  # an async save may still be in flight
                    state = ckpt.restore(state)
                    start_step = int(state.step)
                if pending is not None:
                    t_restore = time.perf_counter() - pending["t_detected"]
                    obs.span_event(
                        "elastic.recover", t_restore, kind=pending["kind"],
                        step=pending["step"], dp_from=pending["dp_from"],
                        dp_to=dp, mp=mp, restore_step=start_step)
                    recoveries.append(dict(
                        kind=pending["kind"], fault_step=pending["step"],
                        restore_step=start_step, dp_from=pending["dp_from"],
                        dp_to=dp, mp=mp, accum=accum,
                        time_to_detect_s=pending["t_detect"],
                        time_to_restore_s=t_restore))
                    print(f"elastic: recovered dp={pending['dp_from']} -> "
                          f"dp={dp} (accum {accum}), restored step "
                          f"{start_step}, detect {pending['t_detect']:.3f}s "
                          f"restore {t_restore:.3f}s")
                    # replayed steps overwrite their tainted records
                    losses = {s: v for s, v in losses.items()
                              if s < start_step}
                    dts = {s: v for s, v in dts.items() if s < start_step}
                    pending = None
                mesh_history.append({"dp": dp, "mp": mp, "accum": accum,
                                     "from_step": start_step})
                if gen > 0:
                    # a re-planned mesh is a new fleet epoch: per-shard step
                    # times legitimately changed (bigger microbatch per
                    # shard), so the straggler baselines must re-learn
                    obs.event("train.straggler.rollup", generation=gen - 1,
                              **straggler.rollup())
                    straggler = ShardStragglerMonitor(warmup=WARMUP_STEPS)

                jit_step = jax.jit(step_fn, donate_argnums=(0,))
                batch_sharding = jax.sharding.NamedSharding(
                    mesh, shd.batch_pspec(mesh))
                loader = SyntheticLoader(cfg, args.batch, args.seq,
                                         sharding=batch_sharding,
                                         seed=args.seed, start=start_step)
                # first telemetry-on step after (re)start: phase probes
                probe_at = (min(start_step + WARMUP_STEPS, args.steps - 1)
                            if gen == 0 else -1)
                status = "done"
                try:
                    for i in range(start_step, args.steps):
                        fault = injector.poll(i) if injector else None
                        if fault is not None and fault.kind == "preempt":
                            obs.event("elastic.fault", kind="preempt",
                                      step=i)
                            print(f"fault: preemption delivered at step {i}")
                            guard.request()
                            fault = None
                        if fault is not None and fault.kind == "straggle":
                            obs.event("elastic.fault", kind="straggle",
                                      step=i, shard=fault.shard,
                                      factor=fault.factor)
                            print(f"fault: shard {fault.shard} straggling "
                                  f"{fault.factor:g}x from step {i}")
                            injector.begin_straggle(fault,
                                                    time.perf_counter())
                            fault = None
                        t_fault = None
                        if fault is not None:  # device_loss
                            t_fault = time.perf_counter()
                            obs.event("elastic.fault", kind="device_loss",
                                      step=i, n_lost=fault.n_devices,
                                      healthy=len(healthy) - fault.n_devices)

                        t_data0 = time.perf_counter()
                        batch = next(loader)
                        obs.span_event("train.step.data",
                                       time.perf_counter() - t_data0, step=i)
                        t0 = time.perf_counter()
                        state, metrics = jit_step(state, batch)
                        loss = float(metrics["loss"])  # blocks on the step
                        dt = time.perf_counter() - t0

                        if t_fault is not None:
                            # the victims died at the step's start; a sync-
                            # SPMD program only surfaces that at the step's
                            # sync point — so detection costs ~one step.
                            # The step's result is tainted: discard it and
                            # go recover from the last checkpoint.
                            t_detect = time.perf_counter() - t_fault
                            obs.span_event("elastic.detect", t_detect,
                                           kind="device_loss", step=i)
                            victims = injector.commit_loss(fault)
                            print(f"elastic: device loss at step {i} "
                                  f"(ids {sorted(victims)}), detected in "
                                  f"{t_detect:.3f}s; re-planning mesh")
                            pending = {"kind": "device_loss", "step": i,
                                       "t_detect": t_detect,
                                       "t_detected": time.perf_counter(),
                                       "dp_from": dp}
                            status = "fault"
                            break

                        straggle = (injector.straggle_active()
                                    if injector else None)
                        dt_clean = dt
                        if straggle is not None and dp > 1:
                            # the slow host finishes late; every shard waits
                            delay = (straggle.factor - 1.0) * dt_clean
                            time.sleep(delay)
                            dt = dt_clean + delay
                        losses[i] = loss
                        dts[i] = dt
                        obs.span_event("train.step", dt, step=i, loss=loss)
                        if injector is not None and dp > 1:
                            # per-shard telemetry: the straggling shard (if
                            # any) reports the slow time, the healthy ones
                            # their clean time — the fleet view the monitor
                            # sees.  Compile steps are excluded from the
                            # detector feed so they cannot poison the
                            # healthy-baseline EWMA.
                            row = (straggle.shard % dp
                                   if straggle is not None else -1)
                            sverdicts = set()
                            for s in range(dp):
                                dt_s = dt if s == row else dt_clean
                                obs.gauge("train.shard.step_time", dt_s,
                                          shard=s, step=i)
                                if i - start_step >= WARMUP_STEPS:
                                    sverdicts.add(
                                        straggler.record(s, i, dt_s))
                            sverdict = ("replace" if "replace" in sverdicts
                                        else "slow" if "slow" in sverdicts
                                        else "ok")
                        else:
                            obs.gauge("train.shard.step_time", dt,
                                      shard=pid, step=i)
                            sverdict = straggler.record(pid, i, dt)
                        verdict = health.record(
                            i, loss, bool(metrics.get("skipped", 0.0)))
                        if i % args.log_every == 0:
                            print(f"step {i:5d} loss {loss:.4f} "
                                  f"gnorm {float(metrics['grad_norm']):.3f} "
                                  f"dt {dt:.3f}s [{verdict}/{sverdict}]")
                        if straggle is not None and sverdict == "replace":
                            # the controller rotates the slow host's row
                            # out of the next mesh epoch (DESIGN.md §18)
                            row = straggle.shard % dp
                            victims = {d.id for d in
                                       np.ravel(mesh.devices)[row * mp:
                                                              (row + 1) * mp]}
                            t_detect = (time.perf_counter()
                                        - injector.straggle_onset())
                            obs.span_event("elastic.detect", t_detect,
                                           kind="straggle", step=i,
                                           shard=row)
                            print(f"elastic: straggler shard {row} voted "
                                  f"REPLACE at step {i} (ids "
                                  f"{sorted(victims)}), detected in "
                                  f"{t_detect:.3f}s; re-planning mesh")
                            injector.mark_lost(victims)
                            injector.end_straggle()
                            pending = {"kind": "straggle", "step": i,
                                       "t_detect": t_detect,
                                       "t_detected": time.perf_counter(),
                                       "dp_from": dp}
                            status = "fault"
                            break
                        if obs.enabled() and i == probe_at:
                            # one-shot measured breakdown (separately jitted
                            # phase prefixes)
                            probes = make_phase_probes(
                                cfg, mesh=mesh if shard_step else None)
                            for ph, sec in probes(state, batch).items():
                                obs.span_event(f"train.phase.{ph}", sec,
                                               step=i)
                        if (verdict == "restore" and ckpt
                                and ckpt.latest_step() is not None):
                            print("health: restoring last checkpoint")
                            ckpt.wait()
                            state = ckpt.restore(state)
                        if ckpt and (i + 1) % args.ckpt_every == 0:
                            ckpt.save_async(state, i + 1)
                        if guard.preempted():
                            print("preemption: flushing checkpoint and "
                                  "exiting")
                            if ckpt:
                                ckpt.wait()
                                ckpt.save(state, i + 1)
                            status = "preempted"
                            break
                finally:
                    loader.close()
            if status != "fault":
                break
    finally:
        if ckpt:
            ckpt.wait()
        obs.event("train.health.rollup", **health.rollup())
        obs.event("train.straggler.rollup", **straggler.rollup())
    if status == "done" and ckpt:
        ckpt.save(state, args.steps)

    # -- summary ------------------------------------------------------------
    # per-generation median step time, its first WARMUP_STEPS (compile /
    # first-touch) excluded; step s belongs to the LAST generation whose
    # range contains it (replays overwrote the tainted records)
    for g, entry in enumerate(mesh_history):
        lo = entry["from_step"]
        hi = (mesh_history[g + 1]["from_step"]
              if g + 1 < len(mesh_history) else args.steps)
        owned = [s for s in sorted(dts) if lo <= s < hi]
        steady = [dts[s] for s in owned[WARMUP_STEPS:]] or \
                 [dts[s] for s in owned]
        entry["steps_run"] = len(owned)
        entry["median_step_s"] = float(np.median(steady)) if steady else None
    for k, rec in enumerate(recoveries):
        pre = mesh_history[k]["median_step_s"]
        post = mesh_history[k + 1]["median_step_s"]
        rec["pre_fault_step_s"] = pre
        rec["post_recovery_step_s"] = post
        if pre and post:
            # per-device throughput retention across the shrink, at fixed
            # global batch: (G / post / dp_to) / (G / pre / dp_from)
            rec["post_shrink_efficiency"] = (
                (pre * rec["dp_from"]) / (post * rec["dp_to"]))

    steps_run = sorted(losses)
    loss_list = [losses[s] for s in steps_run]
    summary = {
        "arch": cfg.name, "steps": args.steps, "global_batch": args.batch,
        "status": status, "first_step": steps_run[0] if steps_run else None,
        "last_step": steps_run[-1] if steps_run else None,
        "losses": loss_list, "recoveries": recoveries,
        "mesh_history": mesh_history,
    }
    if steps_run:
        measured = ([dts[s] for s in steps_run[WARMUP_STEPS:]]
                    or [dts[s] for s in steps_run])
        steady = float(np.median(measured))
        dp_last = mesh_history[-1]["dp"] if mesh_history else 1
        tput = args.batch / steady if steady > 0 else float("nan")
        summary.update(steady_step_s=steady, samples_per_s=tput)
        first = (np.mean(loss_list[:3]) if len(loss_list) >= 6
                 else loss_list[0])
        last = np.mean(loss_list[-3:])
        print(f"done: loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'}); "
              f"steady step {steady:.3f}s over {len(measured)} "
              f"post-warmup steps "
              f"({tput:.2f} samples/s, {tput / dp_last:.2f}/device over "
              f"dp={dp_last})")
    return summary


def main(argv=None):
    run(argv)
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    raise SystemExit(main())
