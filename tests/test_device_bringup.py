"""Bring-up pieces (PR 22): one compile cache placeable from outside, no
silent CPU serving, a /health that names the device, peaks that refuse an
unknown accelerator, and the phase runner of chip_smoke.py."""

import json
import os
import subprocess
import sys
import threading
import types

import jax
import pytest

from cake_tpu.utils import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config(monkeypatch):
    """setup_compile_cache writes jax.config; put back what it found."""
    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
    )
    before = {n: getattr(jax.config, n) for n in names}
    # The suite itself runs with JAX_PLATFORMS=cpu, where the cache stays
    # off; these tests ask what an accelerator run would get.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_cache_dir_from_the_environment_is_left_to_jax(
    cache_config, monkeypatch, tmp_path
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    assert device.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == "sentinel"


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(
    cache_config, monkeypatch
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = device.setup_compile_cache()
    assert first == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert device.setup_compile_cache() == first  # never a pid, a time


def test_no_compile_cache_on_the_cpu(cache_config, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    assert device.setup_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == "sentinel"


def test_cli_refuses_to_serve_from_a_cpu_nobody_asked_for(tmp_path):
    """No TPU, no --cpu, no JAX_PLATFORMS=cpu: JAX drops to the CPU on its
    own, and the CLI must exit non-zero naming the platform instead of
    serving from it. Fails before the model directory is ever read."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "cake_tpu.cli", "--model", str(tmp_path),
         "--api", "127.0.0.1:1", "--api-batch", "8"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert "--cpu" in proc.stderr


def test_health_names_the_device_and_the_attention_impl():
    import urllib.request

    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.runtime.api import ApiServer

    gen = types.SimpleNamespace(config=LlamaConfig.tiny(attention_impl="auto"))
    server = ApiServer(gen, model_name="m").make_server("127.0.0.1", 0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        url = f"http://127.0.0.1:{server.server_address[1]}"
        with urllib.request.urlopen(url + "/health", timeout=30) as r:
            health = json.load(r)
        with urllib.request.urlopen(url + "/stats", timeout=30) as r:
            stats = json.load(r)
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=30)
    assert not thread.is_alive()
    assert health["status"] == "ok"
    assert health["platform"] == "cpu"
    assert health["device_kind"] == jax.devices()[0].device_kind
    assert health["device_count"] == len(jax.devices())
    assert health["jax_version"] == jax.__version__
    assert health["attention_impl"] == "xla"  # what "auto" resolves to here
    assert set(stats["compile"]) == {
        "count", "seconds", "stall_seconds", "by_family", "untracked",
    }


def test_device_peaks_raises_on_an_unknown_accelerator(monkeypatch):
    from cake_tpu.obs import efficiency

    def fake(kind, platform="tpu"):
        dev = types.SimpleNamespace(platform=platform, device_kind=kind)
        monkeypatch.setattr(jax, "devices", lambda *a: [dev])

    fake("TPU v5 lite")
    assert efficiency.device_peaks() == (197.0, 819.0, "TPU v5 lite")
    fake("cpu", platform="cpu")
    assert efficiency.device_peaks() is None
    fake("TPU v99 imaginary")
    with pytest.raises(ValueError, match="TPU v99 imaginary"):
        efficiency.device_peaks()


def _smoke(*args, timeout):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=REPO, capture_output=True, text=True, timeout=timeout,
    )


def test_chip_smoke_seeded_failure_turns_the_exit_code():
    """The phase runner's failure path, cheaply: the probe child runs (one
    process at a time), the seeded phase fails, the exit code is non-zero,
    the phase is named, and no result line is printed."""
    proc = _smoke("--rehearse-cpu", "--phases", "A", "--seed-failure", "A",
                  timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    lines = proc.stdout.strip().splitlines()
    assert all("platform=cpu" in ln for ln in lines), proc.stdout
    assert any("phase=A FAILED" in ln for ln in lines)
    assert lines[-1].endswith("FAILED phases: A")
    assert not any(ln.startswith("{") for ln in lines)


def test_chip_smoke_pool_phase_rehearses():
    """Phase P at the tiny preset: both paged programs compile from shapes
    alone and neither moves the pool (the XLA twins here; the kernels'
    program is compiled for the chip there)."""
    proc = _smoke("--rehearse-cpu", "--phases", "P", timeout=300)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert all("platform=cpu" in ln for ln in lines), proc.stdout
    for program in ("decode", "suffix_join"):
        assert any(
            f"program={program} " in ln and "pool_moving_ops=0" in ln
            for ln in lines
        ), proc.stdout
    assert lines[-1].endswith("not a result)")


def test_pool_write_check_rehearses_at_the_tiny_preset():
    """Phase C's cases of the pool's write (ops/pallas/check.py) and its
    clock, at the tiny preset through the interpreter: every case's bytes
    are the scatter's (tol 0), at the preset's own KV heads and at
    ``write_kv``'s, a decode step and a chunk at each batch."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    from cake_tpu.ops.pallas import check

    preset = chip_smoke.PRESETS["tiny"]
    m = preset["model"]
    kv_heads = (m["num_key_value_heads"], *preset["write_kv"])
    c = check._Cases(check.Geometry(
        hidden=m["hidden_size"], intermediate=m["intermediate_size"],
        n_q=m["num_attention_heads"], n_kv=m["num_key_value_heads"],
        head_dim=m["head_dim_override"], vocab=m["vocab_size"],
        window=m["sliding_window"], page_size=preset["page_size"],
        max_seq=preset["max_seq_len"], chunk=preset["chunk"],
        int4_group=preset["int4_group"], dtype=preset["dtype"],
        batches=tuple(preset["batches"]), write_kv=tuple(preset["write_kv"]),
    ))
    with check.recorded_interpret() as seen:
        check._pool_write_cases(c, kv_heads)
    assert seen and all(seen), seen  # the kernel ran, in the interpreter
    assert len(c.results) == len(set(kv_heads)) * len(preset["batches"]) * 2
    for rec in c.results:
        assert rec["kernel"] == "paged_pool_write" and rec["tol"] == 0
        assert rec["ok"] and rec["max_err"] == 0, rec
    rows = check.timed_pool_write(
        head_dim=m["head_dim_override"], page_size=preset["page_size"],
        dtype=preset["dtype"], **preset["timed_write"])
    assert [(r["rows"], r["kv_heads"], r["width"]) for r in rows] == [
        tuple(s) for s in preset["timed_write"]["shapes"]]
    assert all(r["kernel_us"] > 0 and r["twin_us"] > 0 for r in rows)


def test_chip_smoke_is_nothing_without_the_repo(tmp_path):
    """Alone in a directory it exits non-zero and prints no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=str(tmp_path),
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_passes_every_phase():
    """The whole script at the tiny preset on the CPU: servers A, B, Bf,
    the kernels in interpret mode, the pool audit, tp 4 and a four-stage
    mesh over four virtual devices. ~2 minutes; merely lacking a chip never selects it
    (the default run on this machine fails at the probe)."""
    default = _smoke(timeout=300)
    assert default.returncode == 1 and "needs 'tpu'" in default.stdout
    assert not any(
        ln.startswith("{") for ln in default.stdout.splitlines()
    )
    proc = _smoke("--rehearse-cpu", "--phases", "setup,A,B,Bf,C,P,D",
                  timeout=1500)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert all("platform=cpu" in ln for ln in lines)
    for phase in ("A", "B", "Bf", "C", "D-tp4", "D-mesh4"):
        assert any(f"phase={phase} ok" in ln for ln in lines), phase
    assert any(
        "kernel=paged_pool_write" in ln and "failed=0" in ln for ln in lines
    )
    assert "rehearsal passed" in lines[-1]
