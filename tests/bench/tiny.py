"""A benchmark checkout in miniature for the CPU tests: the real harness,
readers and peaks table, with configurations and mixes small enough for
the Pallas interpreter."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY = {
    "source": "test", "reference": "bench/reference.py",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "reduced": [],
    "serving": {"num_slots": 4, "max_len": 64, "page_slots": 8,
                "prefill_chunk": 8},
}
MIXES = {
    "closed.ecc": {"loop": "closed", "set_seed": 1,
                   "requests_per_client": 8,
                   "prompt": {"dist": "uniform", "min": 8, "max": 40},
                   "output": {"dist": "uniform", "min": 4, "max": 16},
                   "kv_domain": {"voltage": 0.95, "ecc": True}},
    "open.ecc": {"loop": "open", "set_seed": 2, "cv": 2.0, "warmup_s": 1,
                 "prompt": {"dist": "lognormal", "median": 16,
                            "sigma": 0.6, "min": 4, "max": 40},
                 "output": {"dist": "lognormal", "median": 8,
                            "sigma": 0.6, "min": 2, "max": 16},
                 "kv_domain": {"voltage": 0.95, "ecc": True}},
}


def make_root(tmp: Path, *, gap_sigma: float = 1.0, min_tokens: int = 20,
              rate: float = 8.0, config: dict = TINY) -> Path:
    """A directory laid out as a checkout, holding one cell of ``config``
    per mix (``tiny.closed.ecc``, ``tiny.open.ecc``)."""
    bench = tmp / "bench"
    shutil.copytree(ROOT / "bench" / "metrics", bench / "metrics")
    for d in ("configs", "traffic", "cells"):
        (bench / d).mkdir(parents=True)
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test only")
    (bench / "peaks.json").write_text(json.dumps(peaks))
    (bench / "configs" / "tiny.json").write_text(json.dumps(config))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = []
    for mix, body in MIXES.items():
        (bench / "traffic" / f"{mix}.json").write_text(json.dumps(body))
        name = f"tiny.{mix}"
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": mix, "chips": 1, "why": "test"})
        (bench / "cells" / f"{name}.json").write_text(json.dumps({
            "rate_per_s": rate,
            "check": {"gap_sigma": gap_sigma, "min_tokens": min_tokens,
                      "kv_injection": 1}}))
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
