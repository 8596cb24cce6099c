"""Bring-up guards (ISSUE 21): nothing on the serving path may make a
chip-less or kernel-less run look like success.

All fast; the full-HTTP ``chip_smoke.py --cpu-dry-run`` is the one
``slow`` test at the bottom. (Warmup raising on a kernel that cannot
compile is tests/test_jax_engine.py's
test_warmup_raises_when_a_pallas_program_cannot_compile.)
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import device
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.ops import attention as attn
from dynamo_tpu.telemetry.device_time import HBM_PEAK_GBPS, DeviceTimeTracker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


kernel_matrix = _load(os.path.join(REPO, "scripts", "kernel_matrix.py"),
                      "kernel_matrix")


# ---------- compile cache placed from outside ----------


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them (the test
    session must not start writing a cache)."""
    seen = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: seen.update({k: v}))
    return seen


def test_cache_dir_from_environment_sets_no_other(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert device.configure_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in config_updates


def test_cache_dir_defaults_to_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert device.configure_compile_cache() == want
    assert config_updates["jax_compilation_cache_dir"] == want


def test_cache_dir_identical_in_another_process():
    """No pid, temp name or timestamp: a second process, started from
    another directory, resolves the same path — or it would never hit."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; from dynamo_tpu.engine.device import "
         "configure_compile_cache as c; c(); "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, cwd="/", capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == device.DEFAULT_CACHE_DIR


# ---------- the start-up device check ----------


@pytest.mark.parametrize("platforms,backend,interpret,ok", [
    ("cpu", "cpu", False, True),          # asked for the CPU: tests, dry runs
    ("cpu", "cpu", True, True),           # interpret mode belongs there
    (None, "cpu", False, False),          # jax fell back: no chip, no start
    ("tpu,cpu", "cpu", False, False),     # cpu only as the fallback entry
    ("tpu,cpu", "tpu", False, True),
    (None, "tpu", False, True),
    (None, "tpu", True, False),           # interpreted kernels on a chip
])
def test_serving_device_check(monkeypatch, platforms, backend, interpret, ok):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    for var, val in (("JAX_PLATFORMS", platforms),
                     ("DYN_PALLAS_INTERPRET", "1" if interpret else None)):
        if val is None:
            monkeypatch.delenv(var, raising=False)
        else:
            monkeypatch.setenv(var, val)
    if ok:
        device.check_serving_device()
    else:
        with pytest.raises(RuntimeError):
            device.check_serving_device()


# ---------- auto never selects a kernel Mosaic rejects ----------


def test_auto_routes_rejected_specializations_to_xla(monkeypatch):
    """On a TPU backend ``auto`` is pallas — except the specializations
    the kernel table lists as failing Mosaic, which take the XLA route
    (and say so on the route counter); an explicit ``pallas`` still
    reaches the kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, k, v, bt, pos, ctx = kernel_matrix._paged_inputs("toy", 1, 64, 8)
    sinks = jnp.ones((4,), jnp.float32)
    routes = []
    monkeypatch.setattr(attn, "record_route", routes.append)

    def trace(impl, **kw):
        jax.make_jaxpr(lambda *a: attn.attention(
            *a, impl=impl, layer_idx=jnp.int32(0), **kw))(q, k, v, bt, pos, ctx)
        return routes[-1]

    assert trace("auto") == "flash"
    # the flash kernel takes a sink as its running softmax's first term
    # and compiles; the verify kernel's finalize with one does not
    assert trace("auto", sinks=sinks) == "flash"
    q8 = q[:, :8]
    pos8 = pos[:, :8]
    jax.make_jaxpr(lambda *a: attn.attention(
        *a, impl="auto", layer_idx=jnp.int32(0), sinks=sinks))(
            q8, k, v, bt, pos8, ctx)
    assert routes[-1] == "xla"
    jax.make_jaxpr(lambda *a: attn.attention(
        *a, impl="pallas", layer_idx=jnp.int32(0), sinks=sinks))(
            q8, k, v, bt, pos8, ctx)
    assert routes[-1] == "verify"
    # fp8 page copies: rejected at the toy's 2 kv heads, fine at 8
    for shape, want in (("toy", "xla"), ("llama-3.2-1b", "decode")):
        q, k, v, bt, pos, ctx = kernel_matrix._paged_inputs(
            shape, 1, 1, 8, jnp.float8_e4m3fn)
        assert trace("auto") == want


# ---------- the default-route kernels lower for TPU ----------


@pytest.mark.parametrize(
    "name", [n for n, (default, _) in kernel_matrix.CASES.items() if default])
def test_default_route_kernel_lowers_for_tpu(name):
    """A Mosaic-lowering break at either chip_smoke.py shape is caught
    here on the CPU (Mosaic compilation itself only happens on the chip:
    scripts/kernel_matrix.py there)."""
    assert kernel_matrix.run_case(name, on_tpu=False)["lowers"] is True


# ---------- four chips really means four ----------


def test_random_init_is_born_sharded(monkeypatch):
    """tp=4 on the virtual mesh: no full-size parameter or cache array
    ever sits on one device on its way to its NamedSharding, and device
    0 ends up holding about a quarter of the bytes."""
    from dynamo_tpu.engine.model_runner import ModelRunner

    cfg = EngineConfig(
        model=ModelConfig(
            vocab_size=256, hidden_size=128, intermediate_size=512,
            num_layers=4, num_heads=8, num_kv_heads=4, head_dim=32),
        max_batch_size=2, max_model_len=64, kv_block_size=8,
        num_kv_blocks=64, dtype="float32", tp_size=4,
        prefill_buckets=[64],
    )
    whole_on_one_device = []
    real_put = jax.device_put

    def spy(x, *a, **kw):
        for leaf in jax.tree.leaves(x):
            if (isinstance(leaf, jax.Array) and leaf.nbytes > 1 << 16
                    and len(leaf.sharding.device_set) == 1):
                whole_on_one_device.append((leaf.shape, leaf.nbytes))
        return real_put(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", spy)
    runner = ModelRunner(cfg)
    assert whole_on_one_device == []

    state = (runner.params, runner.kv_cache, runner.sample_state)
    total = sum(x.nbytes for x in jax.tree.leaves(state))
    dev0 = runner.mesh.devices.flat[0]
    on_dev0 = sum(
        s.data.nbytes for x in jax.tree.leaves(state)
        for s in x.addressable_shards if s.device == dev0
    )
    # sharded layers + cache dominate; the embedding, norms and the
    # sampling state are replicated
    assert on_dev0 < 0.3 * total, (on_dev0, total)


# ---------- roofline peak by device_kind ----------


def test_roofline_gauge_only_for_a_known_device_kind():
    known = DeviceTimeTracker(param_bytes=1e9, device_kind="TPU v5 lite")
    assert known.peak_bytes_per_s == HBM_PEAK_GBPS["TPU v5 lite"] * 1e9 == 819e9
    assert "dynamo_engine_roofline_fraction" in known.registry.names()
    unknown = DeviceTimeTracker(param_bytes=1e9, device_kind="cpu")
    unknown.observe("decode", "decode", 0.0, 1.0, read_bytes=1e9, tokens=1)
    assert "dynamo_engine_roofline_fraction" not in unknown.registry.names()
    assert "dynamo_engine_roofline_fraction" not in unknown.registry.render()


# ---------- benchmark/run.py prints only what a chip measured ----------


@pytest.mark.parametrize("asks", ["JAX_PLATFORMS=cpu",
                                  "DYN_PALLAS_INTERPRET=1"])
def test_benchmark_refuses_to_run_without_a_chip(asks):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "DYN_PALLAS_INTERPRET")}
    env.update([asks.split("=")])
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "phi3-chat", "--seed", "0", "--seconds", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert "{" not in out.stdout


# ---------- chip_smoke.py ----------


def _smoke(args, cwd=REPO, script=None, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "DYN_PALLAS_INTERPRET")}
    env.update(env_over)
    return subprocess.run(
        [sys.executable, script or os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600,
    )


def test_chip_smoke_without_a_chip_fails_and_prints_no_result():
    out = _smoke([], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil

    script = shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _smoke([], cwd=tmp_path, script=script)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.slow
def test_chip_smoke_cpu_dry_run_passes_its_own_checks():
    out = _smoke(["--cpu-dry-run"])
    assert out.returncode == 0, out.stdout[-4000:]
    assert out.stdout.startswith("DRY RUN")
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {"dry_run": True, "harness_checks_passed": True}
    assert '"ok"' not in out.stdout
