"""Device time of the traced window by the program's layer scopes.

The program names its layers with ``jax.named_scope``: ``layer.stem``,
``layer.res<i>.conv1`` and ``.conv2``, ``layer.head_signal`` and
``layer.head_peak`` around each conv layer, forward and backward, and
``step.loss``, ``step.optimizer`` and ``step.guard`` around the rest of
the train step.  The scope reaches each HLO instruction's ``op_name``,
which a TPU trace keeps as the ``tf_op`` stat of the op's event metadata
(``jit(step)/transpose(jvp(layer.res3.conv1))/conv1d_bwd_data/...``; a
fusion carries its root's).  ``jax.profiler.ProfileData`` does not expose
event metadata, so the ``.xplane.pb`` is read here with its protobuf
schema.

Each device op in the ``bench.window`` span (clipped to it) falls in one
class:

* ``edge``: a conv kernel under ``layer.stem`` or ``layer.head_*``;
* ``body``: any other conv kernel;
* ``glue``: a non-kernel, non-collective op under a ``layer.*`` scope;
* ``update``: an op under a ``step.*`` scope;
* ``unscoped``: anything else, a collective under a ``layer.*`` scope
  among it (the one-chip cells have none).
"""
from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path
from typing import NamedTuple

from benchmarks.chip.lib import spec as specmod, trace as tr

# the directory ``run.py`` traces the window into
TRACE_DIR = specmod.ROOT / ".bench_out" / "trace"
CLASSES = ("edge", "body", "glue", "update", "unscoped")
EDGE_LAYERS = ("layer.stem", "layer.head_")
OP_NAME_STAT = "tf_op"
SCOPE = re.compile(r"(?<![\w.])((?:layer|step)\.\w+(?:\.\w+)*)")


class ScopedEvent(NamedTuple):
    kind: str        # "device" | "host"
    where: str       # device id, or host line name
    name: str
    start_ns: float
    end_ns: float
    op_name: str     # the op's HLO op_name ("" on the host)


def _xplane_pb2():
    """The XPlane protobuf module installed with TensorFlow, loaded from
    its file so that TensorFlow itself is not imported."""
    tf = importlib.util.find_spec("tensorflow")
    if tf is None or tf.origin is None:
        raise ModuleNotFoundError("reading op names needs the XPlane schema "
                                  "installed with tensorflow")
    path = Path(tf.origin).parent / "tsl/profiler/protobuf/xplane_pb2.py"
    spec = importlib.util.spec_from_file_location("_chipbench_xplane_pb2",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def newest(log_dir: Path) -> Path | None:
    """The newest ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    paths = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    return paths[-1] if paths else None


def load(path: Path) -> list[ScopedEvent]:
    """Every op of each chip's "XLA Ops" line in the ``.xplane.pb`` at
    ``path``, with its op name, and every host event."""
    space = _xplane_pb2().XSpace()
    space.ParseFromString(Path(path).read_bytes())
    events = []
    for plane in space.planes:
        dev = tr.DEVICE_PLANE.fullmatch(plane.name)
        if dev is None and not plane.name.startswith("/host:"):
            continue
        op_stat = next((k for k, v in plane.stat_metadata.items()
                        if v.name == OP_NAME_STAT), None)
        names, op_names = {}, {}
        for k, meta in plane.event_metadata.items():
            names[k] = meta.name
            op_names[k] = next((s.str_value.rstrip(":") for s in meta.stats
                                if s.metadata_id == op_stat), "")
        for line in plane.lines:
            if dev is not None and line.name != tr.OPS_LINE:
                continue
            kind, where = (("device", dev.group(1)) if dev is not None
                           else ("host", line.name))
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps / 1e3
                events.append(ScopedEvent(
                    kind, where, names.get(ev.metadata_id, ""), start,
                    start + ev.duration_ps / 1e3,
                    op_names.get(ev.metadata_id, "") if dev else ""))
    return events


def scope(op_name: str) -> str | None:
    """The innermost ``layer.*`` or ``step.*`` scope of an op name."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def classify(name: str, op_name: str) -> str:
    s = scope(op_name)
    if tr.stem(name) in tr.CONV_STEMS:
        return "edge" if s and s.startswith(EDGE_LAYERS) else "body"
    if s is not None and s.startswith("step."):
        return "update"
    if s is not None and not tr.is_collective(name):
        return "glue"
    return "unscoped"


def split(events) -> dict | None:
    """Seconds of device time in the ``bench.window`` span by class,
    summed over the chips, with ``devices``, or None where no op carries
    a scope (a program without layer scopes)."""
    spans = [e for e in events
             if e.kind == "host" and e.name == tr.WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {tr.WINDOW_SPAN!r} span")
    w0 = min(e.start_ns for e in spans)
    w1 = max(e.end_ns for e in spans)
    out = dict.fromkeys(CLASSES, 0.0)
    devices, scoped = set(), False
    for e in events:
        if e.kind != "device" or e.end_ns <= w0 or e.start_ns >= w1:
            continue
        devices.add(e.where)
        scoped = scoped or scope(e.op_name) is not None
        out[classify(e.name, e.op_name)] += (
            min(e.end_ns, w1) - max(e.start_ns, w0)) / 1e9
    if not scoped:
        return None
    out["devices"] = len(devices)
    return out


def read_window(trace_dir: Path) -> dict | None:
    """``split`` of the newest profile under ``trace_dir`` (None if there
    is none), reduced once per profile file however many readers ask."""
    path = newest(trace_dir)
    if path is None:
        return None
    return _split_file(path, path.stat().st_mtime_ns)


@functools.lru_cache(maxsize=1)
def _split_file(path: Path, mtime_ns: int) -> dict | None:
    return split(load(path))


def per_step_ms(r: dict, cls: str) -> float | None:
    """Milliseconds of class ``cls`` per train step of the traced run,
    averaged over the chips; None without a trace or without scopes."""
    if r["trace"] is None or r["steps"] <= 0:
        return None
    s = read_window(TRACE_DIR)
    if s is None:
        return None
    return 1e3 * s[cls] / s["devices"] / r["steps"]
