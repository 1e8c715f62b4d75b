"""chip_smoke.py and the rules it stands on (tier-1, CPU).

- the smoke itself at ``--tiny`` size under an explicit ``JAX_PLATFORMS=cpu``:
  every phase passes, every report line names the platform, and a second run
  in the same cache directory reports compile-cache hits;
- no accelerator and no explicit request for the CPU: ``chip_smoke.py``
  and an ``hbam`` device verb both exit non-zero, and the smoke refuses
  the CPU without ``--tiny`` even when the variable is set;
- the compile-cache placement rule (``utils/backend.enable_compile_cache``);
- the native artifact's digest name: a foreign ``.so`` at the old fixed name
  is never loaded, the name follows source / flags / CPU, a failed build
  keeps the compiler's message.
"""
import json
import logging
import os
import shutil
import subprocess
import sys

import pytest

from hadoop_bam_tpu.utils import backend, native

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                        "JAX_ENABLE_COMPILATION_CACHE", "XLA_FLAGS")}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


def _run(argv, env, timeout=600):
    return subprocess.run([sys.executable] + argv, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


# ---------------------------------------------------------------------------
# the smoke, tiny, on the CPU
# ---------------------------------------------------------------------------

def test_chip_smoke_tiny_cpu_passes_and_second_run_hits_cache(tmp_path):
    env = _env(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = tmp_path / "out"
    argv = [SMOKE, "--tiny", "--out", str(out), "--scratch", str(tmp_path)]
    first = _run(argv, env)
    assert first.returncode == 0, first.stdout[-4000:] + first.stderr[-2000:]
    lines = first.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 2}}
    report_lines = [ln for ln in lines if ln.startswith("[")]
    assert report_lines and all(
        "platform: cpu device_kind: cpu devices: 2" in ln
        for ln in report_lines)
    report = json.loads((out / "chip_smoke_report.json").read_text())
    assert [p["phase"] for p in report["phases"]] == [
        "1-environment", "2-host-feed", "0-fixture", "3-scan",
        "4-sort-mkdup", "5-serve", "6-compile-cache"]
    assert all(p["ok"] for p in report["phases"]), report["phases"]
    by = {p["phase"]: p for p in report["phases"]}
    assert by["1-environment"]["compile_cache_from_env"] is True
    assert by["3-scan"]["seq_stats_kernel"] == "xla-twin"   # cpu: no Mosaic
    assert by["3-scan"]["demotions"] == 0
    assert by["4-sort-mkdup"]["sort_bytes_spill"]["rounds"] >= 3
    assert by["4-sort-mkdup"]["mkdup"]["duplicates_marked"] > 0
    assert by["6-compile-cache"]["total_entries_written"] > 0
    # nothing of the run is left in the scratch parent but out/ + cache/
    assert sorted(p.name for p in tmp_path.iterdir()) == ["cache", "out"]

    second = _run(argv, env)
    assert second.returncode == 0, second.stdout[-4000:]
    report2 = json.loads((out / "chip_smoke_report.json").read_text())
    cache2 = {p["phase"]: p for p in report2["phases"]}["6-compile-cache"]
    assert cache2["total_cache_hits"] > 0
    # same seed, same answers: the sorted / duplicate-marked bytes match
    by2 = {p["phase"]: p for p in report2["phases"]}
    for job in ("sort_index", "sort_bytes", "sort_bytes_spill", "mkdup"):
        assert by2["4-sort-mkdup"][job]["sha256"] \
            == by["4-sort-mkdup"][job]["sha256"]


# ---------------------------------------------------------------------------
# no accelerator, no explicit CPU: every entry point refuses
# ---------------------------------------------------------------------------

def _no_result_line(stdout: str) -> bool:
    return not any(ln.startswith("{") and '"ok": true' in ln
                   for ln in stdout.splitlines())


def test_chip_smoke_refuses_without_accelerator(tmp_path):
    out = str(tmp_path / "out")
    # JAX falls back to the CPU on its own: not asked for, so refused
    r = _run([SMOKE, "--tiny", "--out", out], _env())
    assert r.returncode != 0 and _no_result_line(r.stdout), r.stdout
    # the chip check proper never accepts the CPU, variable or not
    r = _run([SMOKE, "--out", out], _env(JAX_PLATFORMS="cpu"))
    assert r.returncode != 0 and _no_result_line(r.stdout), r.stdout
    assert "no TPU" in r.stderr
    assert not os.path.exists(out)


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in _env(JAX_PLATFORMS="cpu").items()
           if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py", "--tiny"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0 and _no_result_line(r.stdout)
    assert "not importable" in r.stderr


def test_device_verb_refuses_silent_cpu_fallback(tmp_path):
    bam = tmp_path / "absent.bam"
    r = _run(["-m", "hadoop_bam_tpu.tools.cli", "summarize", str(bam)],
             _env())
    assert r.returncode != 0
    assert "JAX_PLATFORMS=cpu" in r.stderr


def test_require_backend_accepts_the_requested_cpu():
    # conftest asked for the CPU through JAX_PLATFORMS
    assert backend.cpu_requested()
    assert backend.require_backend() == "cpu"


# ---------------------------------------------------------------------------
# the compile-cache placement rule
# ---------------------------------------------------------------------------

@pytest.fixture()
def config_updates(monkeypatch):
    import jax

    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    return calls


def test_compile_cache_env_set_means_no_directory_in_code(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert backend.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in config_updates


def test_compile_cache_env_unset_uses_the_fixed_checkout_path(
        monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert backend.enable_compile_cache() == want
    assert config_updates["jax_compilation_cache_dir"] == want
    # fixed: no temp name, pid or time in it, and the same every call
    assert backend.enable_compile_cache() == want


# ---------------------------------------------------------------------------
# the native artifact: digest-named, never foreign, errors kept
# ---------------------------------------------------------------------------

@pytest.fixture()
def fresh_native(monkeypatch, tmp_path):
    """The loader pointed at an empty build directory, its process-wide
    state restored afterwards."""
    monkeypatch.setattr(native, "_OUT_DIR", str(tmp_path / "build"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_info", {"path": None, "flavour": None,
                                          "error": None})
    return tmp_path


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_foreign_so_at_the_old_name_is_ignored(fresh_native):
    build = fresh_native / "build"
    build.mkdir()
    old = build / "libhbam_native.so"
    old.write_bytes(b"built on another machine")
    assert native.load() is not None
    info = native.build_info()
    assert info["error"] is None
    assert os.path.basename(info["path"]) != old.name
    assert info["flavour"] in ("libdeflate", "zlib")
    assert info["flavour"] in os.path.basename(info["path"])
    assert old.read_bytes() == b"built on another machine"


def test_artifact_name_follows_source_flags_and_cpu(monkeypatch, tmp_path):
    name, extra = native._FLAVOURS[-1]
    base = native.artifact_path(name, extra)
    assert base == native.artifact_path(name, extra)
    monkeypatch.setattr(native, "_host_cpu_signature",
                        lambda: "another cpu|sse2")
    other_cpu = native.artifact_path(name, extra)
    monkeypatch.undo()
    assert native.artifact_path(name, extra + ["-DX"]) != base
    src = tmp_path / "hbam_native.cpp"
    with open(native._SRC, "rb") as f:
        src.write_bytes(f.read() + b"\n// edited\n")
    monkeypatch.setattr(native, "_SRC", str(src))
    assert len({base, other_cpu, native.artifact_path(name, extra)}) == 3


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_failed_build_keeps_the_compilers_message(fresh_native, monkeypatch,
                                                  caplog):
    src = fresh_native / "broken.cpp"
    src.write_text("this is not C++;\n")
    monkeypatch.setattr(native, "_SRC", str(src))
    with caplog.at_level(logging.ERROR, logger=native.__name__):
        assert native.load() is None
    err = native.build_info()["error"]
    assert "error" in err and "broken.cpp" in err
    assert "native library build failed" in caplog.text
    assert not native.available()
