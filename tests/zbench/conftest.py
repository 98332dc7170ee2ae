"""A temporary copy of the benchmark with a tiny configuration, a mix and a
cell added as a later PR would add them: new files and new entries, no edit
of a file that is there. The rehearsals run ``bench.run`` from that copy.

That holds for a new architecture too: ``TINY_MOE_MODEL`` is of a
``model_type`` the committed benchmark has no file for, and
``add_architecture`` copies its file (``tests/zbench/architectures``) into
the copy's ``bench/architectures/``, where the configuration's
``model_type`` finds it. ``bench/architectures/__init__.py`` says what such
a file gives."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

TINY_MODEL = {
    "architectures": ["MistralForCausalLM"], "model_type": "mistral",
    "bos_token_id": 1, "eos_token_id": 2, "hidden_act": "silu",
    "hidden_size": 128, "intermediate_size": 256, "initializer_range": 0.02,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16,
    "num_hidden_layers": 4, "max_position_embeddings": 512,
    "rms_norm_eps": 1e-05, "rope_theta": 10000.0, "sliding_window": 48,
    "tie_word_embeddings": False, "vocab_size": 512,
}


# Sparse experts with a shared one behind a gate, q/k/v biases, ChatML, and
# special ids that are not the lowest of the vocabulary. Weights of 0.1 and
# not 0.02: at this width an MLP of 0.02 adds little to the residual, and a
# reference with a faulty expert block would change few of the largest logits.
TINY_MOE_MODEL = {
    "architectures": ["Qwen2MoeForCausalLM"], "model_type": "qwen2_moe",
    "bos_token_id": 480, "eos_token_id": 482, "hidden_act": "silu",
    "hidden_size": 128, "intermediate_size": 256, "initializer_range": 0.1,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "num_hidden_layers": 2, "max_position_embeddings": 512,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": False,
    "moe_intermediate_size": 64, "shared_expert_intermediate_size": 96,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "use_sliding_window": False,
    "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
    "tie_word_embeddings": False, "vocab_size": 512,
}


def tiny_config(chips: int, flags: list[str], model: dict = TINY_MODEL) -> dict:
    return {
        **model, "source": "a test's own", "reduced": [], "assumed": [],
        "deployment": {"chips": chips, "layout": "test"},
        "served_dtype": "bf16", "weights_seed": 3, "server_flags": flags,
        "judge": {"tolerance": 0.25, "rehearsal_tolerance": 0.005, "why": "test"},
    }


ONE_CHIP_FLAGS = [
    "--api-batch", "4", "--max-seq-len", "256", "--kv-mode", "paged",
    "--page-size", "128", "--scheduler", "continuous", "--prefix-cache", "on",
    "--attention-impl", "pallas", "--temperature", "0", "--repeat-penalty", "1.0",
    "--decode-chunk", "8", "--an-option-a-later-pr-deleted", "7",
]
OPEN_LOOP = {"loop": "open", "arrivals": {"process": "poisson", "rate_per_s": 3.0},
             "drain_s": 30.0, "warmup": {"alone_points": 3, "mix_seconds": 1.0}}
CLOSED_LOOP = {"loop": "closed", "clients": 4, "pool": 12, "lead_in_s": 5.0,
               "min_send_gap_s": 0.01, "cold_pass_factor": 1.0, "warmup": {"alone_points": 0}}


def tiny_mix(loop: dict) -> dict:
    return {
        **loop, "order_seed": 1,
        "prompt_tokens": {"dist": "lognormal", "mu": 3.4, "sigma": 0.6, "min": 8, "max": 120},
        "output_tokens": {"dist": "uniform", "min": 6, "max": 12},
        "sharing": None,
    }


def add_cell(root: Path, name: str, config_name: str, config: dict,
             mix_name: str, mix: dict) -> None:
    """What a later PR does: files of its own and entries in BENCHMARK.json.
    Metrics without a ``workloads`` key are every cell's, so also this one's."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / f"bench/configs/{config_name}.json").write_text(json.dumps(config))
    (root / f"bench/traffic/{mix_name}.json").write_text(json.dumps(mix))
    (root / f"bench/workloads/{name}.json").write_text(json.dumps({
        "config": config_name, "traffic": mix_name,
        "chips": config["deployment"]["chips"], "probe_prompt_tokens": [12, 60],
    }))
    bench["configs"].append({
        "name": config_name, "source": "a test's own", "reduced": [], "why": "test",
        "file": f"bench/configs/{config_name}.json",
    })
    bench["workloads"].append({
        "name": name, "config": config_name, "traffic": mix_name,
        "chips": config["deployment"]["chips"], "why": "test",
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def add_architecture(root: Path, model_type: str) -> None:
    """What a later PR does for a ``model_type`` the benchmark has not met."""
    shutil.copy(REPO / f"tests/zbench/architectures/{model_type}.py",
                root / f"bench/architectures/{model_type}.py")


def file_hashes(root: Path) -> dict[str, str]:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def vocabulary(config: dict, root: Path = REPO):
    """The words and the template of ``config`` as a cell of ``root`` has them."""
    from bench.manifest import architecture
    from bench.tokens import Vocabulary

    return Vocabulary(architecture(root, config), config)


def copy_benchmark(dst: Path) -> Path:
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def run_bench(root: Path, *args: str, timeout: float = 300) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([str(root), str(REPO)])
    return subprocess.run(
        [sys.executable, "-m", "bench.run", *args], cwd=root, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def last_json(stdout: str) -> dict:
    """The result line of a run."""
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    """The copy, with the program linked in beside it."""
    root = copy_benchmark(tmp_path_factory.mktemp("bench_copy"))
    (root / "cake_tpu").symlink_to(REPO / "cake_tpu")
    return root
