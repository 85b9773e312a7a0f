"""Examples stay runnable: drive the CPU-only walkthroughs as real
subprocesses (docs and code drift apart silently otherwise; the jax
examples are exercised by the benchmark configs instead)."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(name, *args, timeout=240, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([_REPO, env.get("PYTHONPATH", "")])
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "examples", name), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=_REPO,
    )


def test_iterative_example_runs_and_reports_latency():
    out = _run_example("iterative_example.py")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done: latency per worker" in out.stdout


def test_policy_tuning_example(tmp_path):
    """The sim/ plane walkthrough: record -> replay -> tune, numpy-only
    and fast by construction (virtual time), so it runs in tier-1."""
    out = _run_example("policy_tuning.py", str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "fresh sets reproduced 100% of epochs" in out.stdout
    assert "counterfactual nwait=" in out.stdout
    assert "tuner recommends nwait=" in out.stdout
    assert "(agree)" in out.stdout  # sim cross-check == model pick
    assert "policy tuning ok" in out.stdout
    assert (tmp_path / "straggling_run.jsonl").exists()


def test_router_demo_example():
    """The serving-tier router walkthrough: a seeded diurnal day priced
    per policy on virtual time, numpy-only and seconds by construction
    (like policy_tuning), so it runs in tier-1."""
    out = _run_example("router_demo.py")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "winner:" in out.stdout
    assert "better than round_robin" in out.stdout
    assert "(bit-identical)" in out.stdout
    assert "router demo ok" in out.stdout


def test_disaggregated_demo_example():
    """The round-16 disaggregation walkthrough: unified decode-p99
    collapse vs two-tier stability on the same burst day, the swept
    split, and the bit-identity witness — numpy-only virtual time, so
    it runs in tier-1."""
    out = _run_example("disaggregated_demo.py")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "swept split:" in out.stdout
    assert "better than unified at equal chips" in out.stdout
    assert "(bit-identical)" in out.stdout
    assert "disagg demo ok" in out.stdout


def test_elastic_fleet_demo_example():
    """The round-18 control-plane walkthrough: the autoscaled +
    coordinator-killed diurnal day vs static peak provisioning, with
    the decision timeline and the bit-identity witness — numpy-only
    virtual time, seconds by construction, so it runs in tier-1."""
    out = _run_example("elastic_fleet_demo.py")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "decision timeline:" in out.stdout
    assert "takeovers survived: 1" in out.stdout
    assert "x less" in out.stdout  # the chip-time multiple
    assert "(bit-identical)" in out.stdout
    assert "elastic fleet demo ok" in out.stdout


def test_multi_tenant_demo_example():
    """The round-19 QoS walkthrough: three contracts on one fleet,
    the 10x flood shed by name, the compliant p99 barely moving while
    the FIFO contrast explodes, and the bit-identical replay digest —
    numpy-only virtual time, so it runs in tier-1."""
    out = _run_example("multi_tenant_demo.py")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "shed by name:" in out.stdout
    assert "compliant p99 shift under the flood:" in out.stdout
    assert "NO QoS plane (FIFO, equal chips)" in out.stdout
    assert "replayed bit-identically" in out.stdout
    assert "multi-tenant qos ok" in out.stdout


def test_chaos_demo_example():
    """The round-20 chaos walkthrough: three catalog episodes through
    the injector with invariants armed — overload shed by name, the
    storm + correlated kill + partition combo with non-metastable
    recovery, and the PagePool churn — plus the bit-identical replay
    digest. Numpy-only virtual time, so it runs in tier-1."""
    out = _run_example("chaos_demo.py")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "all by name (100% named)" in out.stdout
    assert "client resubmissions (the storm):" in out.stdout
    assert "partitions begun/healed: 2" in out.stdout
    assert "drops: 0" in out.stdout
    assert "invariants held:" in out.stdout
    assert "replayed bit-identically" in out.stdout
    assert "chaos demo ok" in out.stdout


def test_device_coord_demo_example():
    """The round-17 device-coordination walkthrough: the host-loop vs
    fused-K=64 overhead race plus the bit-identical straggling-fleet
    repochs parity leg — small CPU jit programs, seconds warm (the
    demo shares the suite's persistent compile cache), so it runs in
    tier-1."""
    out = _run_example("device_coord_demo.py")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "overhead multiple:" in out.stdout
    assert "(bit-identical)" in out.stdout
    assert "device coord demo ok" in out.stdout


@pytest.mark.slow
def test_straggler_aware_training_converges(tmp_path):
    out = _run_example("straggler_aware_training.py", str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "respawned" in out.stdout  # the injected crash was recovered
    assert "adaptive nwait settled at" in out.stdout
    assert (tmp_path / "training_trace.json").exists()  # Perfetto artifact


@pytest.mark.slow
def test_rateless_gemm_example():
    out = _run_example(
        "rateless_gemm.py", env_extra={"JAX_PLATFORMS": "cpu"}
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "fixed window: epoch never becomes decodable" in out.stdout
    assert "re-tasks contributed fresh information" in out.stdout


@pytest.mark.slow
def test_pipeline_training_example():
    out = _run_example(
        "pipeline_training.py", timeout=420,
        env_extra={"JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "loss decreased" in out.stdout
    assert "1F1B bubble" in out.stdout


@pytest.mark.slow
def test_long_context_training_example():
    out = _run_example(
        "long_context_training.py", "--steps", "4",
        env_extra={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "remat=on adamw" in out.stdout
    # the virtual mesh must actually materialize: a single-device
    # dp=1 sp=1 tp=1 run would exercise no sharding
    assert "over 8 devices" in out.stdout, out.stdout[-500:]
    assert "sp=4" in out.stdout


@pytest.mark.slow
def test_coded_transformer_training_example():
    out = _run_example(
        "coded_transformer_training.py",
        env_extra={"JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    # timing ratio is load-dependent (shared device) — the deterministic
    # claims are that both loops ran and the trajectories are identical
    assert "coded epochs (nwait=4)" in out.stdout
    assert "bulk-sync epochs (nwait=6)" in out.stdout
    assert "exact full-batch gradient from fastest 4/6: ok" in out.stdout


@pytest.mark.slow
def test_hedged_serving_example():
    out = _run_example(
        "hedged_serving.py", env_extra={"JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    # the example asserts internally that no hedged request paid a
    # stall while single-assignment did; this line prints only then
    assert "the tail is gone" in out.stdout


@pytest.mark.slow
def test_serving_decode_example():
    out = _run_example(
        "serving_decode.py",
        env_extra={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
    )
    assert out.returncode == 0, out.stderr[-2000:]
    # the sharded KV-cache generation really ran on the 8-device mesh
    # with the GQA cache, and matched the dense oracle exactly
    assert "mesh dp=2 tp=4" in out.stdout, out.stdout[-500:]
    assert "kv cache heads: 2 vs 8 MHA" in out.stdout
    assert "sharded generation == dense oracle: ok" in out.stdout
    assert "int8 KV cache:" in out.stdout
    assert "sharded == dense oracle: ok" in out.stdout  # ring section


@pytest.mark.slow
def test_observability_demo(tmp_path):
    out = _run_example(
        "observability_demo.py", str(tmp_path),
        env_extra={"JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "open in ui.perfetto.dev" in out.stdout
    assert "observability demo ok" in out.stdout
    # the live telemetry plane really served HTTP: metrics + healthz
    # scraped, worker pids in /trace, the flight ring dumped
    assert "live: ObsServer on http://127.0.0.1:" in out.stdout
    assert "healthz ok, 3 worker pids in /trace" in out.stdout
    # round 22: the causal-tracing section printed a waterfall that
    # crossed a migration, re-fetched it over real HTTP, and the
    # conservation audit passed
    assert "waterfall:" in out.stdout
    assert "migrate_out" in out.stdout and "adopt" in out.stdout
    assert "reproduced ttft/latency exactly" in out.stdout
    assert "GET /audit ok" in out.stdout
    # round 24: the SLO section's injected latency regression fired
    # the fast-burn alert and the heal cleared it — the timeline
    # printed with both transitions, the cost ledger attributed the
    # day, and /slo + /series served the same state over real HTTP
    assert "alert timeline:" in out.stdout
    assert "fire  ttft-p99" in out.stdout
    assert "clear ttft-p99" in out.stdout
    assert "cost ledger attributed" in out.stdout
    assert "GET /slo ok=True" in out.stdout
    assert "GET /series mirrors" in out.stdout
    # the artifacts really exist and the trace is valid trace-event JSON
    import json

    doc = json.loads((tmp_path / "unified_trace.json").read_text())
    assert any(
        e.get("name", "").startswith("tick ")
        for e in doc["traceEvents"]
    )
    # worker-process task spans merged into the unified timeline
    assert any(
        e.get("name", "").startswith("task e")
        for e in doc["traceEvents"]
    )
    fdoc = json.loads((tmp_path / "flight.json").read_text())
    assert any(
        e.get("ph") == "I" and "postmortem" in e.get("name", "")
        for e in fdoc["traceEvents"]
    )
    prom = (tmp_path / "metrics.prom").read_text()
    assert "serving_ttft_seconds_bucket" in prom


@pytest.mark.slow
def test_continuous_batching_example():
    out = _run_example(
        "continuous_batching.py",
        env_extra={"JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "all 10 streams == their single-request oracles" in out.stdout
    assert "wave 2:" in out.stdout  # straggling admissions exercised
