"""The measuring paths' contract on a machine without the chip: a bench or
the chip smoke that needs the accelerator exits non-zero naming the platform
JAX found (never a ``value: 0.0`` result with exit code 0), the compile
cache can be placed from outside, peaks come from one table keyed by
``device_kind``, and ``chip_smoke.py --rehearse`` runs every phase on the
CPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _run(script: str, *args: str, timeout: float = 240.0):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, str(REPO / script), *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_measuring_paths_refuse_the_cpu(script):
    """No chip, no flag: non-zero exit, the platform named, no result."""
    proc = _run(script)
    assert proc.returncode != 0
    assert "JAX found platform 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_chip_smoke_rehearsal_runs_every_phase():
    proc = _run("chip_smoke.py", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary, verdict = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    # the last line is the verdict, with exactly the keys the driver parses
    assert verdict == {"ok": True, "device": summary["device"]}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["count"], int)
    result = summary
    assert result["ok"] is True and result["rehearsal"] is True
    phases = result["phases"]
    assert list(phases) == ["device", "train", "serve_default",
                            "serve_dispatch", "kernels", "twins"]
    assert all(p["ok"] for p in phases.values())
    assert phases["serve_dispatch"]["recompiles_after_warmup"] == 0
    assert phases["serve_dispatch"]["masks_max_mismatch_frac"] == 0.0
    # interpret mode and XLA share their arithmetic: exact on the CPU
    twins = phases["twins"]["twins"]
    assert all(twins[k]["bitwise"] for k in (
        "deproject", "spline_design", "curvature", "mask_pack",
        "jpeg_decode"))


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    import jax

    from robotic_discovery_platform_tpu.utils import platforms

    # a CPU-pinned process (this one) is left alone
    assert platforms.enable_compile_cache() is None
    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_traceback_in_locations_limit")}
    monkeypatch.setattr(platforms, "_cpu_pinned", lambda: False)
    try:
        # placed from outside: no directory is set in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert platforms.enable_compile_cache() == str(tmp_path)
        assert (jax.config.jax_compilation_cache_dir
                == saved["jax_compilation_cache_dir"])
        # unset: the fixed in-checkout path, the same on every call
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(REPO / ".jax_cache")
        assert platforms.enable_compile_cache() == want
        assert platforms.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        # one frame per location: Mosaic payloads hash the same from every
        # entry point, and named scopes still reach op_name
        assert jax.config.jax_traceback_in_locations_limit == 1
        assert jax.config.jax_include_full_tracebacks_in_locations
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)


def test_peaks_are_looked_up_by_device_kind():
    from robotic_discovery_platform_tpu.utils import flops

    v5e = flops.chip_peaks("TPU v5 lite")
    assert (v5e.bf16_tflops, v5e.hbm_gbps) == (197.0, 819.0)
    assert flops.mfu(197e12, 2.0, v5e) == pytest.approx(0.5)
    with pytest.raises(ValueError, match="device_kind 'cpu'"):
        flops.chip_peaks("cpu")


def test_local_replicas_are_cpu_by_argument():
    from robotic_discovery_platform_tpu.serving import replica

    with pytest.raises(ValueError, match="one process per chip"):
        replica.spawn_local_replicas(1, "file:/nonexistent", force_cpu=0)


# -- autotune populate pass (tools/pallas_autotune.py) -----------------------


def _autotune():
    sys.path.insert(0, str(REPO / "tools"))
    import pallas_autotune

    return pallas_autotune


def _row(op="bspline_design", n=6400, c=16, pallas_ms=1.0, xla_ms=2.0,
         **extra):
    return {"op": op, "n": n, "c": c, "pallas_ms": pallas_ms,
            "xla_ms": xla_ms, **extra}


def test_autotune_extracts_measured_winners():
    at = _autotune()
    bench_payload = {"geometry": [
        _row(pallas_ms=1.0, xla_ms=2.0),                 # pallas wins
        _row(op="bspline_curvature", n=100, c=16,
             pallas_ms=3.0, xla_ms=1.0),                 # xla wins
        {"op": "deproject_edge_stats", "h": 240, "w": 320, "stride": 2,
         "pallas_ms": 1.0, "xla_ms": 1.01},              # noise band
    ]}
    entries, rejected = at.extract_overrides(bench_payload)
    assert rejected == []
    assert entries["bspline_design:c16:n6400"]["impl"] == "pallas"
    assert entries["bspline_curvature:c16:n100"]["impl"] == "xla"
    # inside the 3% band: no override written, default policy runs
    assert not any(k.startswith("deproject:") for k in entries)


def test_autotune_keys_match_lookup_impl():
    """The whole point: what the tool writes is what resolve_impl reads."""
    from robotic_discovery_platform_tpu.ops.pallas import tuning

    at = _autotune()
    entries, _ = at.extract_overrides({"geometry": [
        {"op": "deproject_edge_stats", "h": 480, "w": 640, "stride": 1,
         "pallas_ms": 1.0, "xla_ms": 2.0},
    ]})
    key = tuning.op_key("deproject", h=480, stride=1, w=640)
    assert key in entries


def test_autotune_rejects_malformed_rows():
    at = _autotune()
    bench_payload = {"geometry": [
        _row(pallas_ms=None),                       # analytic-only row
        _row(pallas_ms=0.0),                        # not a time
        _row(pallas_ms=float("nan")),               # non-finite
        _row(op="conv3x3_bn_relu"),                 # not a geometry op
        {"op": "bspline_design", "n": "6400", "c": 16,
         "pallas_ms": 1.0, "xla_ms": 2.0},          # dim not an int
        "not a dict",
        _row(),                                     # the one good row
    ]}
    entries, rejected = at.extract_overrides(bench_payload)
    assert len(entries) == 1
    assert len(rejected) == 6
    # a section that is not a row list is nothing-to-tune, not a crash
    entries, rejected = at.extract_overrides({"geometry": {}})
    assert entries == {} and len(rejected) == 1
    entries, rejected = at.extract_overrides({})
    assert entries == {} and len(rejected) == 1


def test_autotune_merge_owns_geometry_keys_only():
    at = _autotune()
    existing = {
        "conv3x3:b1:32x32:512->512:bfloat16": {"tile_h": 8},
        "bspline_design:c16:n6400": {"impl": "xla"},   # stale verdict
        "deproject:h480:stride1:w640": {"impl": "pallas"},  # now noise
    }
    new = {"bspline_design:c16:n6400": {"impl": "pallas"}}
    merged = at.merge_table(existing, new)
    # conv tile entries ride along untouched
    assert merged["conv3x3:b1:32x32:512->512:bfloat16"] == {"tile_h": 8}
    # owned keys replaced by this pass's verdict...
    assert merged["bspline_design:c16:n6400"]["impl"] == "pallas"
    # ...including DROPPING a stale override not re-confirmed
    assert "deproject:h480:stride1:w640" not in merged
    diff = at.diff_tables(existing, merged)
    assert diff["removed"] == ["deproject:h480:stride1:w640"]
    assert diff["changed"] == ["bspline_design:c16:n6400"]


def test_autotune_dry_run_writes_nothing(tmp_path, capsys, monkeypatch):
    from robotic_discovery_platform_tpu.ops.pallas import tuning

    at = _autotune()
    bench_file = tmp_path / "PALLASBENCH.json"
    bench_file.write_text(json.dumps({"geometry": [_row()]}))
    tune_path = tmp_path / "PALLAS_TUNE.json"
    monkeypatch.setattr(tuning, "_TUNE_PATH", tune_path)
    monkeypatch.setattr(at.tuning, "_TUNE_PATH", tune_path)
    tuning.invalidate_cache()
    try:
        rc = at.main(["--bench", str(bench_file), "--dry-run"])
        assert rc == 0
        assert not tune_path.exists()
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["dry_run"] is True
        assert out["geometry_overrides"] == 1
        # a real run writes the table and lookup_impl serves it
        rc = at.main(["--bench", str(bench_file)])
        assert rc == 0
        assert tune_path.exists()
        assert tuning.lookup_impl(
            "bspline_design", c=16, n=6400) == "pallas"
        # unreadable bench file fails structured
        assert at.main(["--bench", str(tmp_path / "missing.json")]) == 1
    finally:
        tuning.invalidate_cache()
