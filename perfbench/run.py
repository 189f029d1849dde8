#!/usr/bin/env python3
"""perfbench/run.py: one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
`BENCHMARK.json`, its configuration in `configs/<config>.json`, its
traffic in `traffic/<traffic>.json`, the traffic's driver in
`drivers/<driver>.py`, each per-layer metric in `layer_metrics/<name>.json`
and its reader in `readers/<kind>.py`.  Adding a cell, a configuration or
a metric adds files and one manifest entry and edits nothing here.

A run starts the configuration's daemons (the volume server through
`volume_entry.py`, the one process on the chip), lets the driver set up
and warm up (`setup_s`), measures for `--seconds`, then checks what the
window produced against the plain reference.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.  The last line of stdout is the result object and nothing more;
its last key, `compared`, and the last lines of stderr hold every number
that was compared, beside its limit.

`--rehearse` (tests only) runs the traffic file's `rehearse` sizes on the
CPU backend and says so; `--control <name>` (tests and the control runs
only) breaks one guarantee under the harness so that `correct` must come
out false.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import cluster as cluster_mod  # noqa: E402
from cluster import BenchFailure  # noqa: E402

CONTROLS = ("get_body", "shard_file")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(root: str, name: str, rehearse: bool) -> dict:
    """The cell's manifest entry, configuration, traffic and the
    per-layer metrics that list it, all found by name under `root`."""
    manifest = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json "
                           f"(there are: {sorted(cells)})")
    cell = cells[name]
    bench = os.path.join(root, "perfbench")
    config = load_json(bench, "configs", cell["config"] + ".json")
    traffic = load_json(bench, "traffic", cell["traffic"] + ".json")
    if rehearse:
        traffic.update(traffic.get("rehearse", {}))
    reports = lambda m: "workloads" not in m or name in m["workloads"]  # noqa: E731
    layer = []
    for m in manifest["per_layer"]:
        if reports(m):
            spec = load_json(bench, "layer_metrics", m["name"] + ".json")
            layer.append({**m, "reader": spec["reader"]})
    return {"cell": cell, "config": config,
            "traffic": traffic, "layer_metrics": layer,
            "end_to_end": [m for m in manifest["end_to_end"] if reports(m)]}


class Run:
    """What a driver sees of one run."""

    def __init__(self, args, loaded: dict, workdir: str, cache_dir: str):
        self.name = args.workload
        self.seed = args.seed
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self._control = args.control
        self.window_open = False
        self.config = loaded["config"]
        self.traffic = loaded["traffic"]
        self.expect = dict(self.config["expect"])
        if self.rehearse:
            self.expect.update(self.config.get("rehearse_expect", {}))
        self.workdir = workdir
        self.cache_dir = cache_dir
        self.cluster = cluster_mod.Cluster(self.config, workdir, cache_dir,
                                           self.rehearse)
        self.device: dict | None = None
        self.spans: dict[str, list[tuple[float, float]]] = {}
        self.records: dict[str, list[dict]] = {}
        self.counts: dict[str, float] = {}
        self.admin: dict[str, list[dict]] = {}
        self._boot: threading.Thread | None = None
        self._boot_error: list[BaseException] = []

    # -- what drivers call ---------------------------------------------------
    def log(self, msg: str):
        print(f"[{self.name} +{time.perf_counter() - T_START:6.1f}s] {msg}",
              flush=True)

    def span(self, name: str, start: float, end: float):
        if self.window_open:
            self.spans.setdefault(name, []).append((start, end))

    def compare(self, name: str, value, limit) -> dict:
        entry = {"name": name, "value": value, "limit": limit,
                 "ok": bool(value <= limit)}
        self.log(f"compared: {json.dumps(entry)}")
        return entry

    def control(self, name: str, target):
        """The control of `How correct is decided`: once, inside the
        window's own path, break the guarantee `--control` names."""
        if self._control != name or not self.window_open:
            return target
        self._control = None
        if name == "get_body":
            self.log("CONTROL: one byte of one GET body flipped")
            return bytes([target[0] ^ 0x01]) + target[1:]
        with open(target, "r+b") as f:
            f.seek(os.path.getsize(target) // 2)
            byte = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([byte[0] ^ 0x01]))
        self.log(f"CONTROL: one byte of {os.path.basename(target)} flipped")
        return target

    def admin_delta(self, path: str) -> dict:
        before, after = self.admin[path]
        return {k: after[k] - before.get(k, 0) for k in after
                if isinstance(after[k], (int, float))
                and not isinstance(after[k], bool)
                and not k.endswith(("_ratio", "_frac"))}

    # -- the daemons, started while the driver makes its data ----------------
    def boot(self, chips: int):
        def go():
            try:
                self.cluster.start()
                self.device = self.cluster.device()
            except BaseException as e:  # re-raised by wait_cluster
                self._boot_error.append(e)

        self._chips = chips
        self._boot = threading.Thread(target=go, name="boot", daemon=True)
        self._boot.start()

    def wait_cluster(self):
        self._boot.join()
        if self._boot_error:
            raise self._boot_error[0]
        d = self.device
        self.log(f"platform: {d['platform']}, device_kind: "
                 f"{d['device_kind']}, count: {d['count']}")
        if d["platform"] != self.expect["platform"]:
            raise NoChip(f"the volume server runs on platform "
                         f"{d['platform']!r}; this run needs "
                         f"{self.expect['platform']!r}")
        if d["count"] < self._chips:
            raise NoChip(f"the cell asks for {self._chips} chips and JAX "
                         f"reports {d['count']}")


class NoChip(BenchFailure):
    pass


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip, from `peaks.json`; a device that
    is not in the table is an error, not a default."""
    peaks = load_json(HERE, "peaks.json")
    if device_kind not in peaks:
        raise BenchFailure(f"no peaks for device kind {device_kind!r} in "
                           f"peaks.json (it has: {sorted(peaks)})")
    return peaks[device_kind]


def cache_entries(path: str) -> int:
    try:
        return sum(1 for _ in os.scandir(path))
    except OSError:
        return 0


def trace_slice(run: Run, seconds: float, out: dict):
    """A few seconds of the window under the profiler, asked of the
    volume server's helper thread; the rest of the window is untouched."""
    lead = min(2.0, seconds * 0.2)
    length = min(5.0, seconds * 0.5)
    time.sleep(lead)
    logdir = os.path.join(run.workdir, "trace")
    out["start"] = run.cluster.control.ask(cmd="trace_start", dir=logdir)
    out["start_perf"] = time.perf_counter()
    time.sleep(max(0.0, length - (time.perf_counter() - out["start_perf"])))
    out["stop"] = run.cluster.control.ask(cmd="trace_stop", timeout=300)
    out["logdir"] = logdir


def read_layer_metrics(run: Run, loaded: dict, ctx: dict) -> dict:
    out = {}
    for m in loaded["layer_metrics"]:
        reader = importlib.import_module("readers." + m["reader"]["kind"])
        value = reader.read(m["reader"], ctx)
        if value is None:
            run.log(f"per-layer {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        run.log(f"per-layer {m['name']} = {float(value)!r} {m['unit']} "
                f"[{m['layer']}; moves {m['moves']}]")
    return out


def execute(args, loaded: dict, run: Run) -> dict:
    chips = loaded["cell"]["chips"]
    driver = importlib.import_module("drivers." + run.traffic["driver"])
    run.log(f"seed {args.seed}, window {args.seconds} s, trace {args.trace}, "
            f"data under {run.workdir}, compile cache {run.cache_dir} "
            f"({cache_entries(run.cache_dir)} entries), host cores "
            f"{len(os.sched_getaffinity(0))}"
            + (", REHEARSAL on the CPU backend: proves the flow, never "
               "the chip" if run.rehearse else ""))
    run.boot(chips)
    state = driver.prepare(run)
    t0 = time.perf_counter()
    os.sync()   # the set-up's dirty pages are not the window's writeback
    run.log(f"os.sync() after set-up took {time.perf_counter() - t0:.3f} s")

    vs = run.cluster.volume
    admin_paths = set(run.traffic.get("admin_snapshots", []))
    prom = None
    if run.trace:
        admin_paths |= {m["reader"]["path"] for m in loaded["layer_metrics"]
                        if m["reader"]["kind"] == "admin_json"}
        prom = [cluster_mod.scrape(vs)]
    for path in admin_paths:
        run.admin[path] = [cluster_mod.call(vs, path)]
    cache_before = cache_entries(run.cache_dir)
    traced: dict = {}
    tracer = None
    if run.trace:
        tracer = threading.Thread(target=trace_slice, name="tracer",
                                  args=(run, args.seconds, traced))
    setup_s = time.perf_counter() - T_START
    run.log(f"set-up done in {setup_s:.3f} s; window opens")
    run.window_open = True
    t_open = time.perf_counter()
    if tracer:
        tracer.start()
    result = driver.window(run, state, args.seconds)
    if tracer:
        tracer.join()
    t_close = time.perf_counter()
    # what JAX built in the volume server between open and close: a new
    # compile (the cache gains an entry) or a load from the cache, which
    # a count of cache files would not see
    late = [b for b in run.cluster.control.ask(cmd="programs")["built"]
            if t_open <= b[0] <= t_close]
    run.counts["programs_built_in_window"] = len(late)
    run.log(f"window closed after {t_close - t_open:.3f} s; programs built "
            f"inside the window: {len(late)} "
            f"{[(name, round(s, 3)) for _, name, s in late]}; compile "
            f"cache entries added: "
            f"{cache_entries(run.cache_dir) - cache_before}")
    for path in admin_paths:
        run.admin[path].append(cluster_mod.call(vs, path))
    if prom:
        prom.append(cluster_mod.scrape(vs))
    memory = run.cluster.control.ask(cmd="memory")

    compared = driver.verify(run, state, result)
    run.window_open = False
    correct = all(c["ok"] for c in compared)

    d = run.device
    device = {"platform": d["platform"], "kind": d["device_kind"],
              "count": d["count"],
              "memory_peak_bytes": memory["memory_peak_bytes"]}
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"]}
    if not run.trace:
        values = dict(result["end_to_end"], setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
                          for m in loaded["end_to_end"]}
        for name, v in out["metrics"].items():
            run.log(f"end-to-end {name} = {v['value']!r} {v['unit']}")
    else:
        import trace_reduce

        peaks = None if run.rehearse else peaks_for(d["device_kind"])
        window_s = (traced["stop"]["stopped_ns"]
                    - traced["start"]["started_ns"]) / 1e9
        # the trace's clock starts where start_trace returned
        zero = traced["start_perf"]
        spans = [(name, s - zero, e - zero)
                 for name, pairs in run.spans.items() for s, e in pairs
                 if e > zero and s - zero < window_s]
        xplane = trace_reduce.find_xplane(traced["logdir"])
        if xplane is None:
            raise BenchFailure("the profiler left no .xplane.pb")
        trace = trace_reduce.reduce_trace(xplane, window_s, spans)
        run.log(f"trace: window {window_s:.3f} s, device busy "
                f"{trace['busy_s']:.6f} s (mean over chips "
                f"{trace['chips']}; per chip {trace['busy_by_chip']}), idle "
                f"share {1 - trace['busy_s'] / window_s:.4f}")
        ctx = {"prom": prom, "admin": run.admin, "spans": run.spans,
               "records": run.records, "counts": run.counts, "trace": trace,
               "peaks": peaks, "log": run.log}
        out["metrics"] = read_layer_metrics(run, loaded, ctx)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = window_s
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
        if args.keep_trace:
            shutil.copytree(traced["logdir"], args.keep_trace,
                            dirs_exist_ok=True)
    out["device"] = device
    # every number compared, beside its limit: the result's last key
    out["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                       for c in compared}
    run.log(f"attempted {out['attempted']}, failed {out['failed']}, "
            f"correct {correct}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU backend (tests)")
    ap.add_argument("--control", choices=CONTROLS, default=None,
                    help="break one guarantee: correct must come out false")
    ap.add_argument("--keep-logs", default="",
                    help="copy the daemons' logs here when the run ends")
    ap.add_argument("--keep-trace", default="",
                    help="copy the profiler's files here (--trace 1)")
    args = ap.parse_args(argv)

    if not os.path.exists(os.path.join(ROOT, "weed.py")):
        print("perfbench: no seaweedfs_tpu checkout around this directory "
              "(weed.py is missing): nothing to run", file=sys.stderr)
        return 1
    try:
        loaded = load_cell(ROOT, args.workload, args.rehearse)
    except (BenchFailure, OSError, ValueError, KeyError) as e:
        print(f"perfbench: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    # the program's own rule for the compile cache (util/platform.py)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    # one build of the native libraries before two daemons race for it
    make = subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                          capture_output=True, text=True)
    if make.returncode != 0:
        print(f"perfbench: make -C native failed:\n{make.stderr[-2000:]}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, ROOT)    # the volume writer, for set-up's data
    workdir = tempfile.mkdtemp(prefix="perfbench_")
    run = Run(args, loaded, workdir, cache_dir)
    out = None
    failure = None
    code = 1
    try:
        out = execute(args, loaded, run)
    except NoChip as e:
        failure, code = f"no chip: {e}", 3
    except (BenchFailure, OSError, subprocess.SubprocessError, KeyError,
            ValueError) as e:
        failure = f"{type(e).__name__}: {e}"
    finally:
        if run._boot is not None:
            run._boot.join(300)   # no daemon is started behind stop()
        stubborn = run.cluster.daemons.stop()
        if args.keep_logs:
            os.makedirs(args.keep_logs, exist_ok=True)
            for name, _ in run.cluster.daemons.procs:
                shutil.copy(os.path.join(workdir, f"{name}.log"),
                            args.keep_logs)
        if failure or stubborn:
            print(run.cluster.daemons.tails(), file=sys.stderr)
        shutil.rmtree(workdir, ignore_errors=True)
    if stubborn:
        failure = (failure or "") + \
            f" daemons ignored SIGTERM and were killed: {stubborn}"
    if failure:
        print(f"perfbench FAILED: {failure}", file=sys.stderr)
        return code
    for name, c in out["compared"].items():     # stderr's last lines
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
