"""Sharded serving end-to-end: runs repro.launch.shard_serve in a
SUBPROCESS (it asks for 8 virtual CPU devices before jax init, which
must not leak into this test process) and asserts the
tentpole contract on REAL SPMD execution over 8 virtual CPU devices:

  * token-identity: every request served through a mesh-sharded
    Scheduler matches the single-device one-shot oracle, for three
    eviction policies x {phased, interleaved} admission, on BOTH an
    8x1 lane-parallel mesh and a 1x8 head-parallel mesh;
  * the swap-out/resume (park + revive) and prefix-cache hit paths
    round-trip sharded state through the host snapshot layout and stay
    token-identical;
  * speculative decoding's exact-replay rollback survives sharding;
  * the exact dispatch-count formula is unchanged (asserted inside the
    driver per case);
  * the hot-loop programs (admit / segment / mixed / resume / extract
    / reset) compile with ZERO cross-shard resharding collectives on the
    lane-parallel mesh — the shard-local admission contract checked on
    the optimized HLO, not trusted from the source.

Each subprocess batches many cases to amortize the ~1 min of SPMD
compilation; docs/serving.md §Sharded serving documents the contract.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.shard_serve", *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO)


def _json(p):
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_parity_lane_parallel_mesh():
    """8x1 mesh (lanes shard over "data"): 3 policies x 2 admission
    modes + park/revive + prefix-cache + speculative, all
    token-identical to the single-device oracle."""
    out = _json(_run(["--devices", "8", "--meshes", "8x1"]))
    assert out["ok"] and out["mode"] == "parity"
    names = [c["case"] for c in out["cases"]]
    for policy in ("trimkv", "streaming_llm", "h2o"):
        assert f"8x1/{policy}/phased" in names
        assert f"8x1/{policy}/interleaved" in names
    assert all(c["ok"] for c in out["cases"]), out["cases"]
    by = {c["case"]: c for c in out["cases"]}
    assert by["8x1/trimkv/park-revive"]["n_swaps"] >= 1
    assert by["8x1/trimkv/park-revive"]["n_resumes"] >= 1
    assert by["8x1/trimkv/prefix"]["n_prefix_hits"] >= 1
    assert by["8x1/trimkv/spec"]["n_spec_tokens"] > 0


@pytest.mark.slow
def test_sharded_parity_head_parallel_mesh():
    """1x8 mesh (8 MHA heads shard over "model", lanes replicated):
    the tensor-parallel direction of the same parity matrix."""
    out = _json(_run(["--devices", "8", "--meshes", "1x8"]))
    assert out["ok"] and out["mode"] == "parity"
    assert all(c["ok"] for c in out["cases"]), out["cases"]
    assert len(out["cases"]) >= 8   # 3 policies x 2 modes + extras


@pytest.mark.slow
def test_hot_loop_hlo_has_no_resharding_collectives():
    """Lane-parallel mesh: the compiled admit / segment / mixed /
    resume / extract / reset programs must contain no all-gather /
    all-to-all / collective-permute (lane-aligned packing, mask-select
    installs and the lane-aligned chunk sub-step keep every dispatch
    shard-local on the lane axis)."""
    out = _json(_run(["--devices", "8", "--meshes", "8x1",
                      "--check-hlo"]))
    assert out["ok"]
    assert set(out["programs"]) == {"segment", "admit", "mixed",
                                    "resume", "extract", "reset"}
    for prog, found in out["resharding_collectives"].items():
        assert not found, (prog, found)
