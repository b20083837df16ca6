#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it holds the chip, builds the embedded deployment the
cell's configuration file describes, asks it for what the configuration
``requires`` of the program (a miss ends the run there, exit code 1, no
result, before anything is generated), loads it, warms the shapes
the cell's traffic uses, measures for ``--seconds``, holds every
response of the window to the plain reference, and prints one JSON
object as its last line.  Without a TPU it exits non-zero and prints no
result.  Everything that belongs to one cell, configuration, traffic
mix or per-layer metric is in a file found by the name BENCHMARK.json
gives; this file names none of them (README.md).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402
import threading                    # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

OUT_DIR = os.path.join(ROOT, "benchmark_out")


def say(what: str, **fields) -> None:
    """A diagnostic line (never the last line of a successful run)."""
    print(json.dumps({"bench": what, **fields}, default=str), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def resolve(spec: dict, workload: str) -> dict:
    """The cell's files, by the names BENCHMARK.json gives.  A
    statement class whose ``semantics.kind`` has no module under
    ``semantics/`` ends the run here, before any set-up."""
    from benchmark import reference
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    for name, cls in traffic["classes"].items():
        try:
            reference.semantics_module(cls["semantics"]["kind"])
        except ValueError as e:
            raise SystemExit(f"traffic {cell['traffic']!r}, class "
                             f"{name!r}: {e}") from None
    here = lambda m: "workloads" not in m or workload in m["workloads"]  # noqa: E731
    # a per-layer metric's file is named by the part of its name before
    # the first ".": one reader definition serves <family>.<suffix> for
    # every end-to-end metric the family is split over
    return {
        "cell": cell,
        "config": load_json(ROOT, files[cell["config"]]),
        "traffic": traffic,
        "harness": load_json(HERE, "harness.json"),
        "end_to_end": [
            {**load_json(HERE, "end_metrics", m["name"] + ".json"), **m}
            for m in spec["end_to_end"] if here(m)],
        "per_layer": [
            {**load_json(HERE, "layer_metrics",
                         m["name"].split(".")[0] + ".json"), **m}
            for m in spec["per_layer"] if here(m)],
    }


# ====================================================================
# one run
# ====================================================================
class TraceWindow:
    """The profiler trace of the first seconds of the window.  The
    profiler is started in set-up (starting it stalls the process) and
    stopped by a thread of its own; a sync annotation ties the
    profile's clock to the wall clock."""

    def __init__(self, trace_dir: str, seconds: float):
        import jax
        from benchmark.reduce_trace import SYNC_NAME
        self.dir, self.seconds = trace_dir, seconds
        self.stop_wall_ns = None
        self.error: Optional[str] = None
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        t = time.perf_counter()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.start_s = time.perf_counter() - t
        self.sync_wall_ns = time.time_ns()
        with jax.profiler.TraceAnnotation(SYNC_NAME):
            pass
        self._thread = None

    def stop_after(self, t0: float) -> None:
        self._thread = threading.Thread(target=self._stop, args=(t0,))
        self._thread.start()

    def join(self) -> None:
        self._thread.join()

    def _stop(self, t0: float) -> None:
        import jax
        wait = t0 + self.seconds - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        self.stop_wall_ns = time.time_ns()
        t = time.perf_counter()
        try:
            jax.profiler.stop_trace()
        except Exception as e:   # noqa: BLE001 — reported; the traced
            self.error = f"{type(e).__name__}: {e}"   # metrics then lack
        self.stop_s = time.perf_counter() - t


def measure(parts: dict, seed: int, seconds: float, trace: bool,
            tiny: bool) -> dict:
    """Set-up, warm-up and the window; returns the evidence the
    comparison and the metrics read.  The deployment is stopped before
    this returns."""
    from benchmark.deploy import (CompileMeter, Deployment, flags_set,
                                  label_data, shipped_defaults)
    from benchmark.workload import Driver, Mix
    import nebula_tpu.cluster           # noqa: F401 — define the flags
    import nebula_tpu.graph.backend_router  # noqa: F401  before the
    import nebula_tpu.tpu.runtime       # noqa: F401   conf values land
    from nebula_tpu.common import tracing
    from nebula_tpu.common.flags import flags
    from nebula_tpu.common.flight import recorder
    import jax

    config, traffic = dict(parts["config"]), dict(parts["traffic"])
    harness = parts["harness"]
    gen_params = dict(config["generator_params"])
    if tiny:
        gen_params.update(config["selfcheck"]["generator_params"])
        traffic.update(traffic.get("selfcheck", {}))
    stages: Dict[str, float] = {}
    run_flags = {**shipped_defaults(), **config["flags"]}
    if trace:
        run_flags.update(harness["trace_flags"])
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    meter = CompileMeter()
    dep = Deployment(config, OUT_DIR)

    def counters() -> dict:
        return {**dep.counters(),
                **{f"compile.{k}": v for k, v in meter.snap().items()}}

    try:
        with flags_set(run_flags):
            # what the configuration requires of the program is asked
            # of the empty deployment, before anything is generated: a
            # program that lacks it ends here in seconds, with no result
            dep.start()
            missing = dep.missing(config.get("requires", {}))
            if missing:
                for what in missing:
                    print(f"configuration {config['name']!r} requires "
                          f"what this program lacks: {what}",
                          file=sys.stderr)
                raise SystemExit(1)
            t = time.perf_counter()
            gen = importlib.import_module(
                f"benchmark.generators.{config['generator']}").generate(
                    gen_params, int(config["structure_seed"]))
            data = label_data(gen, seed)
            del gen
            stages["generate"] = time.perf_counter() - t
            dep.load(data)
            stages.update(dep.stages)
            say("loaded", seed=seed, vertices=data["n_vertices"],
                edges=data["edges"], generated=data["generated_edges"],
                self_loops_dropped=data["self_loops_dropped"],
                duplicates_dropped=data["duplicates_dropped"],
                stages=stages, facts=dep.facts)
            mix = Mix(traffic, data, int(config["structure_seed"]), seed,
                      seconds)
            check = traffic["check"]
            driver = Driver(dep, mix, float(check["keep_share"]),
                            int(check["keep_rows_cap"]), seed)
            at_start = counters()
            t = time.perf_counter()
            # warm-up: one statement of k start vertices for each k (a
            # lane join's program is shaped by the starts it seats in a
            # tick: 9 / 17 / 33 make the 16 / 32 / 64 shapes), bursts of
            # single-start statements, then the mix itself
            warm = traffic["warmup"]
            steps = [(1, int(k)) for k in warm["starts"]] \
                + [(int(k), 1) for k in warm["bursts"]]
            for i, (statements, starts) in enumerate(steps):
                driver.run_burst(mix.warm(statements, starts, i))
            driver.run("warmup", float(warm["seconds"]))
            dep.quiesce(harness["quiesce_threads"], 600.0)
            stages["warmup"] = time.perf_counter() - t
            stages["compile"] = meter.snap()["backend_compile_seconds"]
            warm_records, driver.records, driver.largest = \
                driver.records, [], None
            window = TraceWindow(
                os.path.join(OUT_DIR, "trace"),
                min(float(traffic["trace"]["seconds"]), seconds / 2)) \
                if trace else None
            before = counters()
            wall_minus_perf_ns = time.time_ns() - time.perf_counter() * 1e9
            t0, t_end = driver.run(
                "measured", seconds,
                on_start=window.stop_after if window else None)
            after = counters()
            if window is not None:
                window.join()
            memory = [d.memory_stats() or {} for d in jax.devices()]
            health = dep.health_problems() + driver.errors
            deadline_s = float(flags.get("query_deadline_ms") or 0) / 1e3
            to_wall_us = lambda p: (p * 1e9 + wall_minus_perf_ns) / 1e3  # noqa: E731
            lo_us, hi_us = to_wall_us(t0), to_wall_us(
                max([t_end] + [r["done"] for r in driver.records]))
            trees = []
            if trace:
                for s in tracing.trace_store.summaries():
                    if lo_us <= s["start_us"] <= hi_us:
                        tree = tracing.trace_store.tree(int(s["id"], 16))
                        if tree:
                            trees.append(tree)
            flight = [r for r in recorder.dump(limit=1 << 24)
                      if lo_us <= r.get("time_us", 0) <= hi_us]
    finally:
        dep.stop()
        meter.close()
    return {"data": data, "mix": mix, "records": driver.records,
            "largest": driver.largest, "warm_records": warm_records,
            "counters": {"start": at_start, "before": before,
                         "after": after},
            "stages": stages, "facts": dep.facts, "health": health,
            "memory": memory, "deadline_s": deadline_s, "t0": t0,
            "t_end": t_end, "seconds": seconds,
            "wall_minus_perf_ns": wall_minus_perf_ns, "window": window,
            "trees": trees, "flight": flight,
            "compiled": list(meter.compiled)}


def compare(ev: dict) -> bool:
    """Hold every response of the window to the plain reference and
    the device-served proof; marks each record ``wrong`` / ``late`` /
    ``failed`` and prints each number compared beside its limit."""
    from benchmark import reference
    t = time.perf_counter()
    data, mix, records = ev["data"], ev["mix"], ev["records"]
    graph = reference.Graph(data["src"], data["dst"],
                            data["edge_prop_table"], data["edge_prop_idx"])
    exact = [r for r in records if "answer" in r]
    largest = ev["largest"]
    if largest is not None and "answer" not in records[largest["index"]]:
        exact.append(largest)
    exact_keys = {(r["cls"], r["key"]) for r in exact}
    wanted: Dict[tuple, tuple] = {}     # (cls, key) -> (digest, answer)

    def want(r: dict) -> tuple:
        k = (r["cls"], r["key"])
        if k not in wanted:
            ans = graph.answer(mix.classes[r["cls"]]["semantics"], r["key"])
            wanted[k] = (reference.digest(ans),
                         ans if k in exact_keys else None)
        return wanted[k]

    for r in records:
        r["wrong"] = not r["problem"] and r["digest"] != want(r)[0]
    exact_wrong = sum(1 for r in exact if not reference.same_rows(
        r["answer"], want(r)[1]))
    wrong = sum(1 for r in records if r["wrong"])
    ev["reference_s"] = time.perf_counter() - t
    served_short: List[str] = []
    start, after = ev["counters"]["start"], ev["counters"]["after"]
    for name in {c.get("served_counter") for c in mix.classes} - {None}:
        need = sum(1 for r in ev["warm_records"] + records
                   if not r["problem"]
                   and mix.classes[r["cls"]].get("served_counter") == name)
        moved = after.get(name, 0) - start.get(name, 0)
        if moved < need:
            served_short.append(f"{name} moved {moved} for {need} "
                                f"statements")
    deadline_s = ev["deadline_s"]
    for r in records:
        r["traversal"] = mix.is_traversal(r["cls"])
        r["late"] = bool(deadline_s) and r["done"] - r["due"] > deadline_s
        r["failed"] = bool(r["problem"]) or r["wrong"] or r["late"]
    # a refused or failed statement carries no answer and is held to
    # nothing: a window of those alone has compared nothing
    answered = sum(1 for r in records if not r["problem"])
    ev["compared"] = {
        "digest_mismatches": {"value": wrong, "limit": 0},
        "exact_mismatches": {"value": exact_wrong, "limit": 0},
        "served_counter_short": {"value": len(served_short), "limit": 0},
        "health_problems": {"value": len(ev["health"]), "limit": 0},
        "responses": {"value": len(records), "at_least": 1},
        "answered": {"value": answered, "at_least": 1}}
    say("compared", responses=len(records), distinct_statements=len(wanted),
        digest_mismatches=wrong, digest_mismatch_limit=0,
        exact_compared=len(exact), exact_mismatches=exact_wrong,
        exact_mismatch_limit=0,
        largest_rows=(largest or {}).get("rows"),
        rows_total=sum(r.get("rows", 0) for r in records),
        served_counter_short=served_short, health=ev["health"],
        refused_or_failed=sum(1 for r in records if r["problem"]),
        first_problems=[r["problem"] for r in records
                        if r["problem"]][:3],
        late=sum(1 for r in records if r["late"]),
        reference_s=ev["reference_s"])
    return not outside_limits(ev["compared"])


def outside_limits(compared: dict) -> List[str]:
    """The numbers compared that lie outside their limit, by name."""
    return [name for name, number in compared.items()
            if number["value"] > number.get("limit", number["value"])
            or number["value"] < number.get("at_least", number["value"])]


def traced_metrics(parts: dict, ev: dict, device: dict, peaks,
                   window: dict) -> tuple:
    """Reduce the profiler trace and run the cell's per-layer readers;
    returns (metrics, missing names, breakdown or None) and adds
    busy_s / window_s to ``device``.  A trace that could not be read
    leaves ``breakdown`` None and every device_trace metric missing."""
    from benchmark import reduce_trace, spans
    trace, records = ev["window"], ev["records"]
    reduced = breakdown = traced_us = None
    path = reduce_trace.newest_trace(trace.dir)
    planes = None if trace.error or path is None else \
        reduce_trace.read_planes(path)
    if planes is None or planes["sync_ns"] is None:
        say("trace_missing", error=trace.error, file=path)
    else:
        off = trace.sync_wall_ns - planes["sync_ns"]
        lo_wall_ns = ev["t0"] * 1e9 + ev["wall_minus_perf_ns"]
        reduced = reduce_trace.reduce(planes, lo_wall_ns - off,
                                      trace.stop_wall_ns - off)
    if reduced:
        host = [(n, s - off, e - off)
                for n, s, e in spans.flat(ev["trees"])]
        host += [("pump.tick", (r["time_us"] - r["dur_us"]) * 1e3 - off,
                  r["time_us"] * 1e3 - off)
                 for r in ev["flight"] if r.get("kind") == "tick"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduce_trace.attribute_gaps(
                         reduced.pop("idle_gaps_ns"), host)}
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
        traced_us = (lo_wall_ns / 1e3, trace.stop_wall_ns / 1e3)
        say("trace", start_s=trace.start_s, stop_s=trace.stop_s,
            programs=reduced["program_s"], runs=reduced["program_runs"],
            file=path, bytes=os.path.getsize(path))
    record = {"trees": ev["trees"], "flight": ev["flight"],
              "counters": ev["counters"],
              "statements_done": sum(1 for r in records
                                     if r["done"] <= ev["t_end"]),
              "stages": ev["stages"], "trace": reduced,
              # the traced interval on the clock of the flight
              # records' time_us, None where there is no trace
              "traced_us": traced_us,
              "facts": ev["facts"], "peaks": peaks,
              # what the end-to-end quantities read (quantities.py)
              "window": window,
              "late_s": [r["sent"] - r["due"] for r in records
                         if ev["mix"].groups[r["group"]]["spec"]["loop"]
                         == "open"]}
    metrics, missing = {}, []
    for m in parts["per_layer"]:
        reader = importlib.import_module(f"benchmark.readers.{m['reader']}")
        value = reader.read(m["select"], record)
        if value is None:
            missing.append(m["name"])
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, missing, breakdown


def run_cell(parts: dict, seed: int, seconds: float, trace: bool,
             device: dict, tiny: bool = False) -> dict:
    """Set up, warm, measure and compare; returns the result object
    (the contract's keys) with ``notes`` beside them.  ``tiny`` applies
    the configuration file's selfcheck sizes (the CPU rehearsal)."""
    from benchmark.bytes_model import peak_for
    from benchmark.quantities import QUANTITIES
    peaks = None if tiny else peak_for(load_json(HERE, "peaks.json"),
                                       device["kind"])
    ev = measure(parts, seed, seconds, trace, tiny)
    correct = compare(ev)
    records = ev["records"]
    peak_bytes = max((m.get("peak_bytes_in_use", 0) for m in ev["memory"]),
                     default=0)
    window = {"records": records, "seconds": seconds, "t_end": ev["t_end"],
              "deadline_s": ev["deadline_s"],
              "start_to_window_s": ev["t0"] - T_PROCESS, "peak_bytes": peak_bytes,
              "edges": ev["data"]["edges"]}
    end_to_end = {}
    for m in parts["end_to_end"]:
        value = QUANTITIES[m["quantity"]](m, window)
        if value is not None:
            end_to_end[m["name"]] = {"value": value, "unit": m["unit"]}
    # one line a statement, for whoever wants another statistic of the
    # window than the metrics take: class, due / sent / done in seconds
    # from the window's start, rows, failed
    with open(os.path.join(OUT_DIR, "statements.jsonl"), "w") as fh:
        for r in records:
            fh.write(json.dumps([ev["mix"].class_names[r["cls"]],
                                 r["due"] - ev["t0"],
                                 r["sent"] - ev["t0"], r["done"] - ev["t0"],
                                 r.get("rows"), r["failed"]]) + "\n")
    before, after = ev["counters"]["before"], ev["counters"]["after"]
    # what a retired per-layer guard watched, on the notes line of a
    # run of its ``cells`` (harness.json "notes": a reader and its
    # select a key; a reader with nothing to read leaves its key out):
    # a share that every run of those cells read as 1.0; never
    # compared, and marked on stderr where it reads anything else
    seen = {"flight": ev["flight"], "counters": ev["counters"],
            "statements_done": sum(1 for r in records
                                   if r["done"] <= ev["t_end"])}
    watched = {name: g for name, g in
               parts["harness"].get("notes", {}).items()
               if parts["cell"]["name"] in g["cells"]}
    guards = {name: importlib.import_module(
        f"benchmark.readers.{g['reader']}").read(g["select"], seen)
        for name, g in watched.items()}
    notes = {"stages": ev["stages"], "reference_s": ev["reference_s"],
             "reference_ms_per_stmt":
             1e3 * ev["reference_s"] / max(len(records), 1),
             "compiles_in_window": after["compile.backend_compiles"]
             - before["compile.backend_compiles"],
             "compiled_in_window": [
                 {"program": name, "at_s": at - ev["t0"], "seconds": secs}
                 for at, name, secs in ev["compiled"] if at >= ev["t0"]],
             **{k: v for k, v in guards.items() if v is not None},
             "guards_off": {k: v for k, v in guards.items()
                            if v not in (None, 1.0)},
             "memory": ev["memory"], "edges": ev["data"]["edges"],
             "end_to_end": end_to_end,
             "late_max_s": max((r["sent"] - r["due"] for r in records),
                               default=0.0),
             "counter_growth": {k: after[k] - before[k] for k in after
                                if k in before and after[k] != before[k]},
             "slowest_ticks": sorted(
                 (r for r in ev["flight"] if r.get("kind") == "tick"),
                 key=lambda r: -r.get("dur_us", 0))[:3],
             "done_per_second": [
                 sum(1 for r in records
                     if i <= r["done"] - ev["t0"] < i + 1)
                 for i in range(int(seconds))]}
    out = {"correct": correct, "attempted": len(records),
           "failed": sum(1 for r in records if r["failed"]),
           "metrics": end_to_end,
           "device": dict(device, memory_peak_bytes=peak_bytes),
           "notes": notes}
    if trace:
        out["metrics"], notes["missing_per_layer"], breakdown = \
            traced_metrics(parts, ev, out["device"], peaks, window)
        if breakdown:
            out["breakdown"] = breakdown
        else:
            notes["trace_missing"] = True
    out["compared"] = ev["compared"]    # last in the line, by contract
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    parts = resolve(load_json(ROOT, "BENCHMARK.json"), args.workload)
    from nebula_tpu.native import ensure_built
    if not ensure_built():
        print("native library build failed", file=sys.stderr)
        return 1
    from nebula_tpu.tpu.jax_setup import device_info, ensure_jax_configured
    ensure_jax_configured()
    info = device_info()
    device = {"platform": info["platform"], "kind": info["device_kind"],
              "count": info["device_count"]}
    if device["platform"] != "tpu" or \
            device["count"] < int(parts["cell"]["chips"]):
        print(f"jax reports {device}: this cell needs "
              f"{parts['cell']['chips']} TPU chip(s); no result",
              file=sys.stderr)
        return 1
    return finish(run_cell(parts, args.seed, args.seconds, bool(args.trace),
                           device), bool(args.trace))


def finish(result: dict, trace: bool) -> int:
    """Print the notes, what stderr owes and the result line; returns
    the exit code.  A listed per-layer metric whose reader found nothing
    is left out of the line and named; a traced run prints no result
    only where the profiler trace could not be read or no per-layer
    metric at all found anything."""
    notes = result.pop("notes")
    say("notes", **notes)
    if notes["compiles_in_window"]:
        # the run is marked, not failed: see PERF.md section 2
        print(f"MARKED: {notes['compiles_in_window']} program(s) compiled "
              f"inside the window: {notes['compiled_in_window']}",
              file=sys.stderr)
    for name, value in notes.get("guards_off", {}).items():
        print(f"MARKED: {name} reads {value} where every run of this "
              f"cell read 1.0 (harness.json, notes)", file=sys.stderr)
    if notes.get("missing_per_layer"):
        # a reader of spans or counters that a later PR adds to the
        # program finds nothing on that PR's parent
        print(f"per-layer metrics with nothing to read, left out: "
              f"{notes['missing_per_layer']}", file=sys.stderr)
    if trace and (notes.get("trace_missing") or not result["metrics"]):
        print("no result: the profiler trace could not be read"
              if notes.get("trace_missing") else
              "no result: no per-layer metric found anything to read",
              file=sys.stderr)
        return 1
    compared = result.pop("compared")
    # what the next writer reckons a cell's room from (README.md, "How
    # long a run may take"): the whole run from process start to this
    # line, and what the reference cost a statement of the window
    result["notes"] = {
        "wall_s": time.perf_counter() - T_PROCESS,
        "reference_s": notes.get("reference_s"),
        "reference_ms_per_stmt": notes.get("reference_ms_per_stmt"),
        "not_correct": outside_limits(compared)}
    result["compared"] = compared       # last in the line, by contract
    for name, number in compared.items():
        print(f"compared {name}: {json.dumps(number)}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
