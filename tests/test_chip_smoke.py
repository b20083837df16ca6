"""Tier-1 coverage of what the chip bring-up added, all on CPU jax at
tiny size: chip_smoke.py's phase-A and phase-B checks (through its
explicit ``--rehearse-cpu`` argument and imported as functions), its
refusal to report without a TPU, and the no-hidden-fallback repairs
around it — compile-cache placement, the mesh-size error, ProcCluster's
device environment, the native build report, the peaks table.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")

import chip_smoke  # noqa: E402 — conftest put the repo root on sys.path


def _run_smoke(args, tmp_path, devices=1, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out"), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=ROOT)


def _cfg(tmp_path, **over):
    cfg = {"seed": 7, "vertices": 600, "edges": 3000, "alpha": 2.2,
           # low degrees keep the sort-based mesh kernels' per-level
           # width (cap x bucket D) affordable on virtual CPU devices
           "max_deg": 12, "parts": 8, "out": str(tmp_path),
           "expect_platform": "cpu"}
    cfg.update(over)
    return cfg


# ------------------------------------------------------- the contract
def test_smoke_fails_without_tpu_and_prints_no_result(tmp_path):
    """No TPU and no rehearsal argument: non-zero exit, nothing on
    stdout (conftest forces JAX_PLATFORMS=cpu, which children
    inherit — exactly the sandbox's situation)."""
    p = _run_smoke(["--vertices", "600", "--edges", "3000"], tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == "", p.stdout
    assert "expected platform 'tpu'" in p.stderr


def test_smoke_parent_stays_off_jax():
    """One process per chip: importing the smoke and everything its
    parent half uses must not import jax."""
    code = ("import sys, chip_smoke, nebula_tpu.native, "
            "nebula_tpu.tools.proc_cluster; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          timeout=120).returncode == 0


def test_smoke_rehearsal_runs_both_phases(tmp_path):
    """The whole script through its explicit CPU rehearsal: both
    phases pass, every line is stamped platform=cpu (never the default,
    never a device result), the mesh leg is an explicit SKIP with one
    device, and the last line is the contract's."""
    p = _run_smoke(["--rehearse-cpu", "--vertices", "2000",
                    "--edges", "16000"], tmp_path)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert all(ln["device"]["platform"] == "cpu" for ln in lines)
    by_event = {}
    for ln in lines[:-1]:
        by_event.setdefault((ln["smoke"], ln["event"]), []).append(ln)
    labels = {ln["label"] for ln in by_event[("phase_a", "statement")]}
    assert {"go1", "go2", "go3", "go4", "go2x32", "where", "count",
            "limit", "upto", "path", "windowed/go2", "windowed/go3",
            "windowed/go4"} <= labels
    assert "skipped" in by_event[("phase_a", "mesh")][0]
    assert by_event[("phase_a", "burst")][0]["continuous"]["max_seats"] > 1
    assert by_event[("phase_a", "insert_read_back")][0][
        "mirror_absorbs"] >= 1
    assert by_event[("phase_a", "summary")][0]["failures"] == []
    assert {ln["label"] for ln in by_event[("phase_b", "statement")]} \
        == {"go1", "go3", "path"}
    assert by_event[("phase_b", "result")][0]["ok"] is True
    # the storaged child logged where its runtime landed
    with open(tmp_path / "out" / "phase_b" / "storaged0.log") as fh:
        assert "runtime on platform=cpu" in fh.read()


# ------------------------------------------- phase A as a function
def _flags_snapshot():
    from nebula_tpu.common.flags import flags
    return flags.dump()


def test_phase_a_passes_with_mesh_leg_and_restores_flags(tmp_path):
    """In-process on the 8-device virtual mesh: the four-device leg
    runs (frontier-sharded GO and BFS counted), and the process-wide
    flags come back as they were."""
    before = _flags_snapshot()
    lines = []
    out = chip_smoke.phase_a(_cfg(tmp_path), lines.append)
    assert out["ok"], out["failures"]
    assert out["device"]["count"] == 8
    mesh = [ln for ln in lines if ln["event"] == "mesh"][0]
    assert mesh["go_mesh_sparse"] > 0 and mesh["bfs_mesh_sparse"] > 0
    assert len(set(mesh["devices"])) == 4
    after = _flags_snapshot()     # lazy imports may DEFINE more
    assert {k: after[k] for k in before} == before


@pytest.mark.parametrize("builder", ["make_continuous_hop_kernel",
                                     "make_batched_sparse_go_kernel"])
def test_phase_a_fails_when_a_kernel_degrades(tmp_path, monkeypatch,
                                              builder):
    """A kernel the compiler refuses becomes a degraded decline: the
    CPU loop answers with ok() true and the right rows.  The smoke's
    check must still FAIL — it reads the warning, and after three
    refusals the open breaker (whose CPU answers carry NO warning) and
    the device counters that stopped moving."""
    import nebula_tpu.tpu.ell as E

    class XlaRuntimeError(RuntimeError):
        pass

    def refuse(*_a, **_k):
        raise XlaRuntimeError("INTERNAL: refused by the compiler")

    monkeypatch.setattr(E, builder, refuse)
    out = chip_smoke.phase_a(_cfg(tmp_path, max_deg=8), lambda _r: None)
    assert not out["ok"]
    fails = out["failures"]
    assert any("degraded" in f for f in fails), fails
    assert any("not counted device-served" in f for f in fails), fails
    assert any("breaker" in f for f in fails), fails
    # ...and nothing claimed the rows were wrong: the CPU loop's were
    assert not any("differ" in f for f in fails), fails


# ------------------------------------------- phase B as a function
def test_phase_b_fails_when_storaged_dies(tmp_path, monkeypatch):
    real_load = chip_smoke._pb_load

    def load_then_kill(cluster, cl, seed):
        S = real_load(cluster, cl, seed)
        cluster.kill("storaged0")
        return S

    monkeypatch.setattr(chip_smoke, "_pb_load", load_then_kill)
    lines = []
    out = chip_smoke.phase_b(_cfg(tmp_path), lines.append)
    assert not out["ok"] and out["failures"]
    assert lines[-1]["event"] == "result" and lines[-1]["ok"] is False


# ----------------------------------------- compile-cache placement
def _config_updates(monkeypatch):
    """Re-arm jax_setup and record every jax.config.update it makes."""
    import jax
    from nebula_tpu.tpu import jax_setup
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setattr(jax_setup, "_done", False)
    jax_setup.ensure_jax_configured()
    return dict(calls)


def test_cache_dir_from_environment_is_left_alone(monkeypatch, tmp_path):
    from nebula_tpu.tpu import jax_setup
    monkeypatch.setenv(jax_setup.CACHE_ENV, str(tmp_path))
    assert jax_setup.compilation_cache_dir() is None
    assert "jax_compilation_cache_dir" not in _config_updates(monkeypatch)


def test_cache_dir_defaults_into_the_checkout(monkeypatch):
    from nebula_tpu.tpu import jax_setup
    monkeypatch.delenv(jax_setup.CACHE_ENV, raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert jax_setup.compilation_cache_dir() == want
    assert _config_updates(monkeypatch)["jax_compilation_cache_dir"] \
        == want


# ------------------------------------------------ no hidden fallback
def test_mesh_larger_than_the_device_count_is_an_error():
    """tpu_mesh_devices=16 on the 8-device virtual mesh: an error at
    mirror build and on every statement — not a warning and a
    single-device (or CPU-loop) answer."""
    from nebula_tpu.cluster import LocalCluster
    from nebula_tpu.common.flags import flags
    from nebula_tpu.tpu.runtime import MeshUnavailable

    c = LocalCluster(num_storage=1, tpu_backend=True)
    try:
        g = c.client()
        assert g.execute(
            "CREATE SPACE big(partition_num=2, replica_factor=1)").ok()
        c.refresh_all()
        assert g.execute("USE big").ok()
        assert g.execute("CREATE EDGE e(w int)").ok()
        c.refresh_all()
        assert g.execute("INSERT EDGE e(w) VALUES 1->2:(1), 2->3:(2)").ok()
        sid = c.graph_meta_client.get_space_id_by_name("big").value()
        assert g.execute("GO 2 STEPS FROM 1 OVER e").ok()
        flags.set("tpu_mesh_devices", 16)
        try:
            with pytest.raises(MeshUnavailable, match="16.*8 device"):
                c.tpu_runtime.mirror(sid)
            for stmt in ("GO 2 STEPS FROM 1 OVER e", "GO FROM 1 OVER e",
                         "FIND SHORTEST PATH FROM 1 TO 3 OVER e"):
                r = g.execute(stmt)
                assert not r.ok() and "tpu_mesh_devices=16" in r.error_msg
        finally:
            flags.set("tpu_mesh_devices", 0)
        assert g.execute("GO 2 STEPS FROM 1 OVER e").ok()
    finally:
        c.stop()


def test_proc_cluster_child_environment(tmp_path):
    """Default: every daemon forced onto CPU jax.  An explicit device
    environment reaches storaged only — graphd and metad are jax-free
    and must never be able to take a chip."""
    from nebula_tpu.tools.proc_cluster import ProcCluster
    c = ProcCluster(str(tmp_path / "a"), start=False)
    assert {d.env["JAX_PLATFORMS"] for d in c.daemons.values()} == {"cpu"}
    c = ProcCluster(str(tmp_path / "b"), start=False,
                    device_env={"JAX_PLATFORMS": "tpu"})
    got = {n: d.env["JAX_PLATFORMS"] for n, d in c.daemons.items()}
    assert got == {"metad": "cpu", "graphd": "cpu", "storaged0": "tpu"}
    c.add_graphd("graphd2", start=False)
    assert c.daemons["graphd2"].env["JAX_PLATFORMS"] == "cpu"


def test_bench_suite_refuses_chip_children_after_jax():
    """This process has imported jax (conftest): handing the chip to a
    storaged child from here would fail or hang it."""
    from nebula_tpu.tools.bench_suite import chip_child_env
    with pytest.raises(RuntimeError, match="already imported jax"):
        chip_child_env()


def test_bench_exits_nonzero_without_tpu():
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                       capture_output=True, text=True, timeout=300,
                       cwd=ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_failed_native_build_is_reported(monkeypatch, capsys):
    from nebula_tpu import native

    def fail(cmd, **_kw):
        raise subprocess.CalledProcessError(
            2, cmd, output="", stderr="kv_engine.cc:1: error: boom\n")

    monkeypatch.delenv("NEBULA_NATIVE_SO", raising=False)
    monkeypatch.setattr(native.subprocess, "run", fail)
    assert native.ensure_built() is False
    err = capsys.readouterr().err
    assert "make -C" in err and "kv_engine.cc:1: error: boom" in err


def test_peaks_are_looked_up_by_device_kind():
    """The declared models are the v5e row of the one peaks table; a
    runtime on a device the table does not know (CPU jax) has no peak
    and folds nothing against one."""
    import types

    from nebula_tpu.tpu import runtime as R
    v5e = R.DEVICE_PEAKS["TPU v5 lite"]
    assert R.MESH_MODEL["hbm_gbps"] == v5e["hbm_gbps"] == 819.0
    assert R.MESH_MODEL["ici_gbps"] * 8 == 1600.0
    assert R.HBM_MODEL["device_hbm_bytes"] == v5e["hbm_bytes"]
    rt = R.TpuQueryRuntime([types.SimpleNamespace(kv=None)], None)
    try:
        assert rt.device_info["platform"] == "cpu"
        assert rt._peaks is None
    finally:
        rt.shutdown()
