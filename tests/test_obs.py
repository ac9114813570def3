"""Telemetry subsystem (repro.obs): schema contract, disabled-path no-op
(no events, no retraces, sub-microsecond hooks), span nesting and its
profiler annotation, counters kept without a sink, kernel/tuner
instrumentation accuracy, straggler detection from gauges, and the two
consumers (scoreboard + Chrome-trace export)."""
import json
import os
import sys
import threading
import timeit
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import obs, tune
from repro.kernels import ops
from repro.obs import report, trace_export
from repro.runtime.health import HealthMonitor
from repro.runtime.straggler import ShardStragglerMonitor


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Every test starts and ends with telemetry disabled and the counter
    totals at zero (the bus and the totals are process-global)."""
    obs.disable()
    obs.reset_counters()
    yield
    obs.disable()
    obs.reset_counters()


def _log(tmp_path, name="t.jsonl"):
    return str(tmp_path / name)


class TestSchema:
    def test_round_trip_all_kinds(self, tmp_path):
        path = obs.enable(_log(tmp_path))
        with obs.span("a.span", note="x"):
            pass
        obs.counter("a.counter", 3)
        obs.gauge("a.gauge", 1.5)
        obs.event("a.event", k="v")
        obs.disable()
        recs = obs.read_events(path)  # strict=True validates every record
        kinds = [r["kind"] for r in recs]
        assert kinds == ["meta", "span", "counter", "gauge", "event"]
        assert recs[0]["name"] == "provenance"
        for key in ("git_sha", "jax_version", "device_kind", "process_index"):
            assert key in recs[0]["attrs"]
        assert recs[2]["value"] == 3 and recs[2]["total"] == 3
        assert recs[3]["value"] == 1.5
        assert recs[4]["attrs"] == {"k": "v"}

    def test_validate_rejects_malformed(self):
        ok = {"kind": "gauge", "name": "g", "ts": 0.0, "attrs": {},
              "pid": 0, "value": 1.0}
        assert obs.validate(dict(ok)) == ok
        with pytest.raises(ValueError):
            obs.validate({**ok, "kind": "bogus"})
        with pytest.raises(ValueError):
            obs.validate({k: v for k, v in ok.items() if k != "value"})
        with pytest.raises(ValueError):
            obs.validate({**ok, "ts": -1.0})
        with pytest.raises(ValueError):
            obs.validate({"kind": "span", "name": "s", "ts": 0.0,
                          "attrs": {}, "pid": 0, "dur": -0.1, "id": 1,
                          "parent": None})

    def test_read_events_rejects_non_json(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text("not json\n")
        with pytest.raises(ValueError, match="not JSON"):
            obs.read_events(str(p))


class TestDisabledPath:
    def test_no_events_no_file(self, tmp_path):
        assert not obs.enabled()
        with obs.span("x", a=1) as s:
            obs.counter("c")
            obs.gauge("g", 1.0)
            obs.event("e")
            obs.span_event("se", 0.1)
        assert s.dur is None  # the shared no-op span
        # counters keep their totals without a sink, and write nothing
        assert obs.counters() == {"c": 1}
        assert obs.log_path() is None
        assert list(tmp_path.iterdir()) == []

    def test_disabled_hooks_under_one_microsecond(self):
        n = 20_000
        obs.distinct("d", "seen")
        # a counter's disabled path updates its in-memory total; every
        # other hook returns at once
        for hook in (lambda: obs.counter("c"),
                     lambda: obs.distinct("d", "seen"),
                     lambda: obs.gauge("g", 1.0),
                     lambda: obs.span("s")):
            # min-of-repeats: scheduler noise only ever inflates a sample
            sec = min(timeit.repeat(hook, number=n, repeat=5)) / n
            assert sec < 1e-6, f"disabled hook cost {sec * 1e9:.0f} ns"

    def test_enable_disable_does_not_retrace(self, tmp_path):
        x = jnp.ones((2, 8, 32))
        w = jnp.ones((3, 4, 8))
        f = jax.jit(lambda x: ops.conv1d(x, w, dilation=2, backend="xla"))
        f(x)
        n0 = f._cache_size()
        obs.enable(_log(tmp_path))
        f(x)
        assert f._cache_size() == n0
        obs.disable()
        f(x)
        assert f._cache_size() == n0

    def test_identical_jaxpr_enabled_vs_disabled(self, tmp_path):
        x = jnp.ones((2, 8, 32))
        w = jnp.ones((3, 4, 8))

        def f(x):
            return ops.conv1d(x, w, dilation=2, backend="pallas")

        off = str(jax.make_jaxpr(f)(x))
        obs.enable(_log(tmp_path))
        on = str(jax.make_jaxpr(f)(x))
        assert on == off


class TestCounters:
    def test_totals_kept_with_and_without_a_sink(self, tmp_path):
        obs.counter("k", 2)
        path = obs.enable(_log(tmp_path))
        obs.counter("k", 3)
        obs.disable()
        obs.counter("k")
        assert obs.counters()["k"] == 6
        [rec] = [r for r in obs.read_events(path) if r["kind"] == "counter"]
        assert rec["value"] == 3 and rec["total"] == 5
        obs.reset_counters()
        assert obs.counters() == {}

    def test_distinct_counts_each_key_once(self):
        for key in ("a", "b", "a", ("c", 1), ("c", 1), "b"):
            obs.distinct("seen", key)
        assert obs.counters()["seen"] == 3
        obs.reset_counters()
        obs.distinct("seen", "a")
        assert obs.counters()["seen"] == 1

    def test_concurrent_increments_are_not_lost(self):
        """More threads than cores, switching often: every increment and
        every first sighting of a key is counted exactly once."""
        n_threads, n_incr = 4 * (os.cpu_count() or 1), 2_000
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            def work(t):
                for i in range(n_incr):
                    obs.counter("stress")
                    obs.distinct("stress.keys", i % 50)

            threads = [threading.Thread(target=work, args=(t,))
                       for t in range(n_threads)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
            assert not any(th.is_alive() for th in threads)
        finally:
            sys.setswitchinterval(interval)
        assert obs.counters()["stress"] == n_threads * n_incr
        assert obs.counters()["stress.keys"] == 50


class TestSpans:
    def test_nesting_parent_chain(self, tmp_path):
        path = obs.enable(_log(tmp_path))
        with obs.span("outer"):
            with obs.span("mid"):
                with obs.span("inner"):
                    pass
            with obs.span("mid2"):
                pass
        obs.disable()
        spans = {r["name"]: r for r in obs.read_events(path)
                 if r["kind"] == "span"}
        assert spans["outer"]["parent"] is None
        assert spans["mid"]["parent"] == spans["outer"]["id"]
        assert spans["inner"]["parent"] == spans["mid"]["id"]
        assert spans["mid2"]["parent"] == spans["outer"]["id"]
        # children are contained in their parents
        o, i = spans["outer"], spans["inner"]
        assert o["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6

    def test_span_event_parented_under_open_span(self, tmp_path):
        path = obs.enable(_log(tmp_path))
        with obs.span("outer"):
            obs.span_event("derived", 0.25, step=3)
        obs.disable()
        spans = {r["name"]: r for r in obs.read_events(path)
                 if r["kind"] == "span"}
        assert spans["derived"]["parent"] == spans["outer"]["id"]
        assert spans["derived"]["dur"] == 0.25

    def test_attrs_set_inside_the_block_are_recorded(self, tmp_path):
        path = obs.enable(_log(tmp_path))
        with obs.span("s", given=1) as sp:
            sp.attrs["found"] = 2
        obs.disable()
        [rec] = [r for r in obs.read_events(path) if r["kind"] == "span"]
        assert rec["attrs"] == {"given": 1, "found": 2}
        assert rec["dur"] == sp.dur > 0

    def test_reenable_same_path_appends(self, tmp_path):
        path = obs.enable(_log(tmp_path))
        obs.event("one")
        assert obs.enable(path) == path  # idempotent
        obs.event("two")
        obs.disable()
        names = [r["name"] for r in obs.read_events(path)]
        assert names == ["provenance", "one", "two"]

    def test_span_appears_in_profiler_trace(self, tmp_path):
        """While a sink is open, a span is also a profiler annotation: it
        shows on the host plane of a ``jax.profiler`` trace."""
        from jax.profiler import ProfileData

        obs.enable(_log(tmp_path))
        trace_dir = tmp_path / "trace"
        jax.profiler.start_trace(str(trace_dir))
        with obs.span("demo.annotated"):
            jnp.ones(8).block_until_ready()
        obs.span_event("demo.after_the_fact", 0.1)
        jax.profiler.stop_trace()
        obs.disable()
        [pb] = Path(trace_dir).glob("plugins/profile/*/*.xplane.pb")
        host = {ev.name
                for plane in ProfileData.from_file(str(pb)).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events}
        assert "demo.annotated" in host
        assert "demo.after_the_fact" not in host


class TestKernelInstrumentation:
    def test_eager_conv_passes_get_measured_spans(self, tmp_path):
        """Eager passes are not timed: each logs its config event, and the
        only spans are the kernel builds."""
        path = obs.enable(_log(tmp_path))
        x = jnp.ones((2, 8, 64))
        w = jnp.ones((3, 4, 8))
        ops.conv1d(x, w, dilation=2, backend="pallas")
        y, pull = jax.vjp(
            lambda w: ops.conv1d(x, w, dilation=2, backend="pallas"), w)
        pull(jnp.ones_like(y))
        obs.disable()
        recs = obs.read_events(path)
        events = {r["name"]: r for r in recs if r["kind"] == "event"}
        assert {"conv1d.fwd.trace", "conv1d.bwd_data.trace",
                "conv1d.bwd_weight.trace"} <= set(events)
        fwd = events["conv1d.fwd.trace"]["attrs"]
        assert fwd["backend"] == "pallas" and not fwd["depthwise"]
        assert (fwd["N"], fwd["C"], fwd["K"], fwd["S"]) == (2, 8, 4, 3)
        for r in recs:
            assert "efficiency" not in r["attrs"]
            assert "gflops_per_s" not in r["attrs"]
        spans = [r for r in recs if r["kind"] == "span"]
        assert {r["name"] for r in spans} == {"kernels.build"}
        # fwd twice (plain and under vjp), bwd_data, bwd_weight
        assert [r["attrs"]["kernel"] for r in spans] == [
            "conv1d_fwd", "conv1d_fwd", "conv1d_bwd_data",
            "conv1d_bwd_weight"]
        assert spans[0]["attrs"]["dtype"] == "float32"
        assert obs.counters()["kernels.build"] == 4
        assert obs.counters()["kernels.build_distinct"] == 3

    def test_traced_conv_logs_trace_event_only(self, tmp_path):
        path = obs.enable(_log(tmp_path))
        x = jnp.ones((2, 8, 64))
        w = jnp.ones((3, 4, 8))
        jax.jit(lambda x: ops.conv1d(x, w, dilation=2, backend="pallas"))(x)
        obs.disable()
        recs = obs.read_events(path)
        assert [r["name"] for r in recs if r["kind"] == "event"] \
            == ["conv1d.fwd.trace"]
        # the one span is the kernel's build at trace time; no pass is timed
        assert [r["name"] for r in recs if r["kind"] == "span"] \
            == ["kernels.build"]

    def test_depthwise_spans(self, tmp_path):
        """Depthwise passes log config events marked depthwise; their
        kernel builds are the only spans."""
        path = obs.enable(_log(tmp_path))
        x = jnp.ones((2, 16, 64))
        w = jnp.ones((4, 16))
        y, pull = jax.vjp(
            lambda w: ops.depthwise_conv1d(x, w, backend="pallas"), w)
        pull(jnp.ones_like(y))
        obs.disable()
        recs = obs.read_events(path)
        events = [r for r in recs if r["name"].startswith("conv1d.")]
        assert {e["name"] for e in events} == {
            "conv1d.fwd.trace", "conv1d.bwd_data.trace",
            "conv1d.bwd_weight.trace"}
        assert all(e["kind"] == "event" and e["attrs"]["depthwise"]
                   for e in events)
        spans = [r for r in recs if r["kind"] == "span"]
        assert {s["attrs"]["kernel"] for s in spans} == {
            "dwconv1d_fwd", "dwconv1d_bwd_data", "dwconv1d_bwd_weight"}


class TestTunerInstrumentation:
    def test_hit_miss_counters_against_prepopulated_cache(self, tmp_path):
        cache = tune.TuneCache(str(tmp_path / "cache.json"))
        shape = dict(N=2, C=8, K=8, S=3, dilation=2, Q=128, dtype="float32")
        tune.tune(**shape, cache=cache, measure=False)  # pre-populate
        path = obs.enable(_log(tmp_path))
        tune.get_config(**shape, cache=cache)                    # hit
        tune.get_config(**shape, cache=cache)                    # hit
        tune.get_config(**{**shape, "Q": 256}, cache=cache)      # miss
        obs.disable()
        totals = {r["name"]: r["total"]
                  for r in obs.read_events(path) if r["kind"] == "counter"}
        assert totals["tune.cache.hit"] == 2
        assert totals["tune.cache.miss"] == 1
        assert "tune.cache.legacy_upgrade" not in totals

    def test_legacy_entry_counts_upgrade(self, tmp_path):
        cache = tune.TuneCache(str(tmp_path / "cache.json"))
        prob = tune.ConvProblem(N=2, C=8, K=8, S=3, dilation=2, Q=128,
                                dtype="float32", padding="VALID",
                                depthwise=False, epilogue="none",
                                pass_="fwd")
        # a pre-§12 entry: no alg/nblk fields
        cache.put(prob.key(tune.device_kind()),
                  {"backend": "xla", "wblk": 64, "kblk": None})
        path = obs.enable(_log(tmp_path))
        cfg = tune.get_config_for(prob, cache=cache)
        obs.disable()
        assert cfg.source == "cache" and cfg.alg is None
        totals = {r["name"]: r["total"]
                  for r in obs.read_events(path) if r["kind"] == "counter"}
        assert totals["tune.cache.legacy_upgrade"] == 1

    def test_search_traces_predicted_vs_measured(self, tmp_path):
        cache = tune.TuneCache(str(tmp_path / "cache.json"))
        path = obs.enable(_log(tmp_path))
        tune.tune(N=2, C=8, K=8, S=3, dilation=2, Q=128, dtype="float32",
                  cache=cache, measure=True, top_k=2, iters=2, warmup=1)
        obs.disable()
        recs = obs.read_events(path)
        cands = [r for r in recs if r["name"] == "tune.search.candidate"]
        assert len(cands) == 2
        for c in cands:
            assert c["attrs"]["predicted_s"] > 0
            assert c["attrs"]["measured_s"] > 0
        [search] = [r for r in recs
                    if r["kind"] == "span" and r["name"] == "tune.search"]
        assert search["attrs"]["candidates"] >= 2


class TestStragglerFromGauges:
    @staticmethod
    def _gauge(shard, step, dt):
        return {"kind": "gauge", "name": "train.shard.step_time",
                "ts": float(step), "pid": 0, "value": dt,
                "attrs": {"shard": shard, "step": step}}

    def test_straggling_shard_detected(self):
        events = []
        for step in range(16):
            events.append(self._gauge(0, step, 0.1))
            # shard 1 degrades persistently after step 12
            events.append(self._gauge(1, step, 1.0 if step >= 12 else 0.1))
        mon = ShardStragglerMonitor(trip=3)
        last = mon.feed_gauges(events)
        assert last[0] == "ok"
        assert last[1] == "replace"
        assert mon.stragglers() == {1}
        roll = mon.rollup()
        assert roll["shards"] == 2 and roll["stragglers"] == [1]
        # the healthy EWMA must not absorb the outliers
        assert mon.detectors[1].healthy_step_time < 0.2

    def test_report_shards_section(self, tmp_path):
        path = obs.enable(_log(tmp_path))
        for step in range(16):
            obs.gauge("train.shard.step_time", 0.1, shard=0, step=step)
            obs.gauge("train.shard.step_time",
                      1.0 if step >= 12 else 0.1, shard=1, step=step)
        obs.disable()
        agg = report.aggregate_path(path)
        assert agg["shards"]["stragglers"] == [1]
        assert agg["shards"]["per_shard"]["0"]["verdicts"] == {"ok": 16}

    def test_health_rollup(self):
        h = HealthMonitor()
        h.record(0, 1.0, skipped=False)
        h.record(1, 1.0, skipped=True)
        roll = h.rollup()
        assert roll["events"] == 1 and roll["by_kind"] == {"skip": 1}
        assert roll["loss_ema"] == pytest.approx(1.0)


class TestConsumers:
    def _write_full_log(self, tmp_path):
        path = obs.enable(_log(tmp_path))
        x = jnp.ones((2, 8, 64))
        w = jnp.ones((3, 4, 8))
        y, pull = jax.vjp(
            lambda w: ops.conv1d(x, w, dilation=2, backend="pallas"), w)
        pull(jnp.ones_like(y))
        obs.counter("tune.cache.hit")
        obs.span_event("train.step", 0.02, step=0)
        obs.span_event("train.phase.forward", 0.005, step=0)
        obs.span_event("train.phase.backward", 0.012, step=0)
        obs.gauge("train.shard.step_time", 0.02, shard=0, step=0)
        obs.disable()
        return path

    def test_report_sections_and_check(self, tmp_path):
        agg = report.aggregate_path(self._write_full_log(tmp_path))
        assert report.check(agg) == []
        assert agg["steps"]["count"] == 1
        assert agg["steps"]["phases"]["forward"]["p50_s"] == 0.005
        assert agg["tuner"]["hits"] == 1
        [cell] = [k for k in agg["conv_cells"] if k.endswith("|bwd_weight")]
        assert agg["conv_cells"][cell]["count"] == 1
        for c in agg["conv_cells"].values():
            assert not {"efficiency_p50", "gflops_per_s_p50",
                        "p50_ms"} & set(c)
        assert agg["counters"]["kernels.build"] == 3
        text = report.render_text(agg)
        assert "train.step" in text and "tuner cache" in text

    def test_check_flags_missing_sections(self, tmp_path):
        path = obs.enable(_log(tmp_path))
        obs.event("nothing.useful")
        obs.disable()
        missing = report.check(report.aggregate_path(path))
        assert len(missing) == 4  # conv, steps, phases, tuner all absent

    def test_report_cli(self, tmp_path, capsys):
        path = self._write_full_log(tmp_path)
        assert report.main([path, "--check"]) == 0
        assert "smoke gate OK" in capsys.readouterr().out
        assert report.main([path, "--json"]) == 0
        agg = json.loads(capsys.readouterr().out)
        assert agg["tuner"]["hits"] == 1

    def test_trace_export(self, tmp_path):
        path = self._write_full_log(tmp_path)
        out = str(tmp_path / "trace.json")
        n = trace_export.export(path, out)
        with open(out) as f:
            trace = json.load(f)
        evs = trace["traceEvents"]
        assert len(evs) == n > 0
        assert trace["metadata"]["provenance"]["jax_version"]
        complete = [e for e in evs if e["ph"] == "X"]
        assert {"kernels.build", "train.step"} <= \
            {e["name"] for e in complete}
        for e in complete:
            assert e["dur"] >= 0 and e["ts"] >= 0
        assert any(e["ph"] == "C" for e in evs)      # counter track
        assert any(e["ph"] == "M" for e in evs)      # process metadata
