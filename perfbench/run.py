"""Run one benchmark cell on the accelerator and print its result line.

    python3 perfbench/run.py --workload qwen25_3b.decode_mix --seed 7 \
        --seconds 30 --trace 0

Run from the root of a checkout. Everything a cell is made of is found by
name: the cell in ``BENCHMARK.json``; its configuration's file; the mix in
``perfbench/traffic/<traffic>.json``; the limits of its output check in
``perfbench/limits/<cell>.json``; each per-layer metric's reader in
``perfbench/metrics/<metric>.py``; the chip's peaks in
``perfbench/lib/peaks.json``. The configuration's ``kind`` picks the
runner (``lib/lm.py`` or ``lib/pricing.py``).

A run sets up (builds, makes the weights from the seed, warms the cell's
shapes from the compile cache), measures for ``--seconds``, reads the
device's peak memory, frees the system's state and checks what the timed
path produced against the plain reference. With ``--trace 0`` the metrics
are the cell's end-to-end metrics; with ``--trace 1`` the window runs
under the profiler and the metrics are the cell's per-layer metrics.

The last lines of standard error give each number checked beside its
limit; the last line of standard output is one JSON object. Without an
accelerator, or with fewer chips than the cell asks for, it exits with 2
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
RUNNERS = {"lm": ("lib.lm", "LMRun"), "pricing": ("lib.pricing", "PricingRun")}


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: pathlib.Path, name: str) -> dict:
    """The cell, its configuration, mix, limits and metrics, by name."""
    bench = _json(root / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def applies(metric):
        return name in metric.get("workloads", [name])

    data = root / "perfbench"
    return {
        "cell": cell,
        "config": _json(root / entry["file"]),
        "traffic": _json(data / "traffic" / f"{cell['traffic']}.json"),
        "limits": _json(data / "limits" / f"{name}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoAccelerator(f"JAX found no accelerator (default device: "
                            f"{devs[0].platform})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


class _CompileCount:
    """Counts the backend compilations JAX reports while it is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def _listen(self, event, *_a, **_kw):
        if event == self.EVENT:
            self.n += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


def _memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def compare(found: dict, limits: dict) -> dict:
    """Each number that has a limit, beside its limit."""
    return {k: {"value": found[k], "limit": limits[k]} for k in limits}


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: pathlib.Path = ROOT, require_accelerator: bool = True,
             prepare=None, control: bool = False,
             readings: bool = False) -> dict:
    """Run one cell; returns the result line as a dict. ``prepare(run)``,
    when given, is called after set-up (the tests and ``control.py`` use
    it to break the timed path). With ``readings`` the result also
    carries every number the check read; with ``control``, besides, the
    comparison made with the control's numbers in place of the program's
    (``perfbench/control.py``)."""
    spec = load_cell(root, name)
    chips = int(spec["cell"]["chips"])
    import jax

    from repro.compile_cache import enable_compile_cache

    devs = _devices(chips) if require_accelerator else jax.devices()[:chips]
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    module, cls = RUNNERS[spec["config"]["kind"]]
    run = getattr(importlib.import_module(module), cls)(
        spec["config"], spec["traffic"], seed, trace)
    run.setup()
    setup_s = time.perf_counter() - T_START
    if prepare is not None:
        prepare(run)

    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    compiles = _CompileCount()
    try:
        if trace:
            jax.profiler.start_trace(trace_dir)
        try:
            with compiles:
                run.window(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        memory = _memory_peak(devs) if require_accelerator else 0
        reduced = None
        if trace:
            from lib import xtrace

            reduced = xtrace.load(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    result = {"device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind,
                         "count": jax.device_count(),
                         "memory_peak_bytes": memory}}
    metrics = {}
    if trace:
        from lib import readers

        peak = _json(HERE / "lib" / "peaks.json")["devices"].get(
            devs[0].device_kind)
        if peak is None:
            raise KeyError(f"no peaks for {devs[0].device_kind!r} in "
                           "perfbench/lib/peaks.json")
        ctx = types.SimpleNamespace(trace=reduced, run=run, peak=peak,
                                    chips=chips)
        for m in spec["per_layer"]:
            value = _reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        busy, window = readers.busy_window(reduced, chips)
        result["device"].update(busy_s=busy, window_s=window)
        result["breakdown"] = readers.breakdown(reduced, chips)
    else:
        measured = run.end_to_end()
        measured["setup_s"] = (setup_s, "s")
        for m in spec["end_to_end"]:
            value, unit = measured[m["name"]]
            if unit != m["unit"]:
                raise ValueError(f"{m['name']}: unit {unit} is not "
                                 f"BENCHMARK.json's {m['unit']}")
            metrics[m["name"]] = {"value": float(value), "unit": unit}

    counts = run.counts()
    run.release()
    t_check = time.perf_counter()
    found = run.check(control=control)
    t_check = time.perf_counter() - t_check
    checks = compare(found, spec["limits"])
    correct = (counts["attempted"] > 0 and counts["failed"] == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result.update(correct=correct, attempted=counts["attempted"],
                  failed=counts["failed"], metrics=metrics, checks=checks)
    phases = ", ".join(f"{k} {v:.3f} s" for k, v in run.phases.items())
    print(f"set-up {setup_s:.3f} s ({phases}); compiles in the window: "
          f"{compiles.n}; output check {t_check:.3f} s; {run.describe()}",
          file=sys.stderr)
    if control:
        low = compare(found.pop("control"), spec["limits"])
        result["control"] = {
            "correct": all(c["value"] <= c["limit"] for c in low.values()),
            "checks": low}
    if control or readings:
        result["readings"] = found
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "readings", "control", "checks"]
    return {k: result[k] for k in order if k in result}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: no system under test at {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoAccelerator as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
