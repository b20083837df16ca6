"""The CPU rehearsal: ``python -m benchmark.selfcheck``.

Every cell of BENCHMARK.json at its configuration's selfcheck size
through the same code as a chip run (CPU jax, said so on every line),
the comparator on weakened answers (one row dropped; one row
duplicated), the reference against the program's own CPU executor, and
the trace reducer on the recorded v5e trace.  It never prints a result
line: a rehearsal is not a measurement.
"""
from __future__ import annotations

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def check_comparator() -> list:
    import numpy as np
    from benchmark import reference
    want = (np.array([5, 3, 3, 9]), np.array([1, 2, 2, 4]))
    same = (np.array([3, 9, 5, 3]), np.array([2, 4, 1, 2]))
    dropped = tuple(c[:-1] for c in want)
    duplicated = tuple(np.append(c, c[0]) for c in want)
    swapped = (np.array([5, 3, 3, 9]), np.array([2, 1, 2, 4]))
    bad = []
    if reference.digest(same) != reference.digest(want) \
            or not reference.same_rows(same, want):
        bad.append("comparator refuses the same rows in another order")
    for name, ans in (("one row dropped", dropped),
                      ("one row duplicated", duplicated),
                      ("two values swapped between rows", swapped)):
        if reference.digest(ans) == reference.digest(want) \
                or reference.same_rows(ans, want):
            bad.append(f"comparator accepts an answer with {name}")
    rows = [(1, "a"), (2, "b")]
    if reference.same_rows(rows[:1], rows) \
            or reference.digest(rows + rows[:1]) == reference.digest(rows):
        bad.append("comparator accepts a weakened tuple answer")
    return bad


def check_recorded_trace() -> list:
    from benchmark import reduce_trace
    path = os.path.join(HERE, "recorded", "v5e_small.xplane.pb")
    want = json.load(open(os.path.join(HERE, "recorded",
                                       "v5e_small.expected.json")))
    got = reduce_trace.reduce(reduce_trace.read_planes(path))
    bad = []
    for key in ("busy_s", "window_s"):
        if abs(got[key] - want[key]) > 1e-9 * max(1.0, want[key]):
            bad.append(f"recorded trace: {key} {got[key]} != {want[key]}")
    for prog, secs in want["program_s"].items():
        if abs(got["program_s"].get(prog, -1) - secs) > 1e-9:
            bad.append(f"recorded trace: program {prog} time differs")
    if got["busy_s"] > got["window_s"]:
        bad.append("recorded trace: busy exceeds the window")
    return bad


def row_multiset(ans) -> list:
    """An answer in either of the reference's forms (int64 columns, or
    a list of row tuples) as its sorted rows: what it says, whatever
    form it came in."""
    if isinstance(ans, list):
        return sorted(tuple(r) for r in ans)
    return sorted(zip(*(c.tolist() for c in ans)))


def check_reference_against_cpu_executor(spec: dict) -> list:
    """The plain reference and the program's own CPU executor
    (``storage_backend=cpu``, what chip_smoke.py compares with) must
    give the same rows on the first statements of every mix.  The rows
    are compared as multisets BEFORE their form: the CPU executor hands
    a filtered GO's rows, and a DISTINCT's, as tuples where the device
    path and the reference hand int64 columns, and says the same."""
    import importlib
    from benchmark import reference, run
    from benchmark.deploy import (Deployment, flags_set, label_data,
                                  shipped_defaults)
    from benchmark.workload import Mix, columns_of
    bad, done = [], set()
    for cell in spec["workloads"]:
        parts = run.resolve(spec, cell["name"])
        config, traffic = parts["config"], parts["traffic"]
        if (cell["config"], cell["traffic"]) in done:
            continue
        done.add((cell["config"], cell["traffic"]))
        params = {**config["generator_params"],
                  **config["selfcheck"]["generator_params"]}
        data = label_data(importlib.import_module(
            f"benchmark.generators.{config['generator']}").generate(
                params, int(config["structure_seed"])), seed=4_000_000_007)
        graph = reference.Graph(data["src"], data["dst"],
                                data["edge_prop_table"],
                                data["edge_prop_idx"])
        dep = Deployment(config, run.OUT_DIR)
        try:
            with flags_set({**shipped_defaults(), **config["flags"],
                            "storage_backend": "cpu"}):
                dep.load(data)
                mix = Mix(traffic, data, int(config["structure_seed"]),
                          4_000_000_007, 2.0)
                client = dep.client()
                seq = mix.groups[0]["measured"]
                for i in range(min(24, len(seq["cls"]))):
                    ci, key = mix.at(seq, i)
                    stmt = mix.statement(ci, key)
                    resp = client.execute(stmt)
                    want = graph.answer(mix.classes[ci]["semantics"], key)
                    if not resp.ok() or row_multiset(columns_of(resp)) \
                            != row_multiset(want):
                        bad.append(f"{cell['name']}: CPU executor and "
                                   f"reference differ on {stmt!r}")
        finally:
            dep.stop()
    return bad


def main() -> int:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import run
    from nebula_tpu.native import ensure_built
    ensure_built()
    spec = run.load_json(ROOT, "BENCHMARK.json")
    bad = check_comparator() + check_recorded_trace() \
        + check_reference_against_cpu_executor(spec)
    for cell in spec["workloads"]:
        parts = run.resolve(spec, cell["name"])
        for trace in (False, True):
            out = run.run_cell(parts, seed=2_345_678_901 + trace,
                               seconds=3.0, trace=trace, device=CPU,
                               tiny=True)
            print(f"[selfcheck cpu] {cell['name']} trace={int(trace)}: "
                  f"correct={out['correct']} attempted={out['attempted']} "
                  f"failed={out['failed']} metrics="
                  f"{sorted(out['metrics'])} missing="
                  f"{out['notes'].get('missing_per_layer')}", flush=True)
            if not out["correct"] or out["failed"]:
                bad.append(f"{cell['name']} trace={int(trace)}: correct="
                           f"{out['correct']} failed={out['failed']}")
            # a CPU run has no device plane: only device_trace metrics
            # may be missing here
            device_metrics = {m["name"] for m in parts["per_layer"]
                              if m["source"] == "device_trace"}
            extra = set(out["notes"].get("missing_per_layer", [])) \
                - device_metrics
            if extra:
                bad.append(f"{cell['name']}: readers found nothing for "
                           f"{sorted(extra)}")
    for b in bad:
        print(f"[selfcheck cpu] FAILED: {b}", flush=True)
    print(f"[selfcheck cpu] {'failed' if bad else 'passed'} "
          f"(platform cpu: no number above is a device number)")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
