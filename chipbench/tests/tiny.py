"""A benchmark root for the CPU tests: a copy of the harness, the program's
sources, and one tiny cell (2 layers, d_model 64) that runs in seconds."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REAL = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "name": "tiny", "source": "https://huggingface.co/stabilityai/stablelm-2-1_6b",
    "arch": "stablelm-1.6b", "num_hidden_layers": 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 128, "vocab_size": 512, "rope_theta": 10000,
    "torch_dtype": "float32", "norm_eps": 1e-6,
    "reduced": ["num_hidden_layers", "hidden_size", "intermediate_size",
                "vocab_size"],
    "serve": {"slots": 2, "max_len": 64, "buckets": [16, 32],
              "decode_steps": 4, "paged": True, "page_size": 16,
              "prefix_sharing": True, "quantize_weights": False, "kv": "none",
              "max_pending": 8},
    "peak": "bf16_flops",
    # float32 on both sides: the program and the reference agree to ~1e-6
    "check": {"gap_max": 1e-3, "control_bits": 8},
}

TINY_TRAFFIC = {
    "kind": "closed_loop", "clients_per_slot": 2,
    "prompt_len": {"dist": "uniform", "min": 4, "max": 16},
    "output_len": {"dist": "uniform", "min": 8, "max": 24},
    "warmup_output_len": {"dist": "uniform", "min": 1, "max": 24},
    "warmup_s": 1, "stagger_s": 0.01, "pool": 64, "check_sample": 3,
}


def make_root(tmp: Path) -> Path:
    root = Path(tmp) / "bench"
    shutil.copytree(REAL / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(REAL / "src")
    write_json(root / "chipbench" / "configs" / "tiny.json", TINY_CONFIG)
    write_json(root / "chipbench" / "traffic" / "tiny_closed.json", TINY_TRAFFIC)
    write_json(root / "BENCHMARK.json", {
        "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": TINY_CONFIG["source"],
                     "file": "chipbench/configs/tiny.json",
                     "reduced": TINY_CONFIG["reduced"], "why": "test"}],
        "workloads": [{"name": "tiny.decode", "config": "tiny",
                       "traffic": "tiny_closed", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "tpot_p50_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock"},
            {"name": "output_tok_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny.decode"]},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"}],
        "per_layer": [
            {"name": "decode_row_occupancy", "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "scheduler",
             "moves": "output_tok_s", "workloads": ["tiny.decode"]},
            {"name": "prefill_pad_share", "unit": "%", "better": "lower",
             "source": "program_counter", "layer": "scheduler",
             "moves": "tpot_p50_ms", "workloads": ["tiny.decode"]}],
    })
    return root


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def server_argv(fault: str) -> list[str]:
    return [sys.executable, str(Path(__file__).with_name("cpu_server.py")),
            fault]
