"""A tiny benchmark tree for CPU tests: the real harness, metrics,
references and traffic laws, with one small configuration and cells
added as files, exactly as a later change would add them."""
import json
import os
import shutil

from chipbench_paths import BENCH

from chipbench import spec

MODEL = {
    "name": "tiny", "family": "dense", "num_layers": 2, "d_model": 64,
    "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
    "vocab_size": 300, "attn_pattern": ["global"], "rope_theta": 10000.0,
    "qkv_bias": True, "tie_embeddings": False, "norm_eps": 1e-6,
    "dtype": "bfloat16", "trimkv": True, "gate_hidden": 32,
}
# the shared serving settings at the tiny size: short chunks and
# segments, so that prompts span several chunks; XLA attention, since
# the Pallas kernels only interpret off the chip
SERVE = {**spec.SERVE, "attn_impl": "xla", "prefill_chunk": 8,
         "decode_segment": 4}
CELL = {"budget": 16, "lanes": 3}
CORRECT = {"max_logit_gap": 0.05, "min_tokens": 20, "sample_tokens": 60,
           "sample_requests": 4}
TRAFFIC = {
    "tiny-open": {"loop": "open", "arrivals": {"law": "gamma", "cv": 2.0},
                  "rate": 6.0, "prompt_len": {"law": "loguniform",
                                              "lo": 4, "hi": 40},
                  "output_len": {"law": "loguniform", "lo": 2, "hi": 20}},
    "tiny-closed": {"loop": "closed", "clients": 3, "requests_per_client": 4,
                    "prompt_len": {"law": "uniform", "lo": 4, "hi": 20},
                    "output_len": {"law": "loguniform", "lo": 8, "hi": 24},
                    "min_left": 2},
}
E2E = [{"name": n, "unit": u, "better": b, "bound": 0.25,
        "source": "host_clock"}
       for n, u, b in (("ttft_p95_s", "s", "lower"),
                       ("tpot_p95_ms", "ms", "lower"),
                       ("output_tok_s", "tokens/s", "higher"),
                       ("peak_hbm_gib", "GiB", "lower"),
                       ("setup_s", "s", "lower"))]


def write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def make_tree(tmp, monkeypatch, extra_metrics=()):
    """A checkout-like root under `tmp` holding BENCHMARK.json and a copy
    of the benchmark's directory with the tiny files added, and the
    tiny serving settings in place for the test. Returns (root,
    bench)."""
    monkeypatch.setattr(spec, "SERVE", SERVE)
    root = str(tmp)
    bench = os.path.join(root, "bench")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    write(os.path.join(bench, "configs", "tiny.json"),
          {"name": "tiny", "source": "tests", "reference": "dense_gqa",
           "model": MODEL})
    cells = []
    for traffic, body in TRAFFIC.items():
        write(os.path.join(bench, "traffic", traffic + ".json"), body)
        name = "tiny." + traffic
        write(os.path.join(bench, "cells", name + ".json"),
              {**CELL, "correct": CORRECT})
        cells.append({"name": name, "config": "tiny", "traffic": traffic,
                      "chips": 1, "why": "test"})
    per_layer = [{"name": "sched.lane_occupancy", "unit": "%",
                  "better": "higher", "source": "program_counter",
                  "layer": "scheduler", "moves": "output_tok_s"}]
    per_layer += list(extra_metrics)
    write(os.path.join(root, "BENCHMARK.json"), {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "tests",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": cells, "end_to_end": E2E, "per_layer": per_layer})
    return root, bench
