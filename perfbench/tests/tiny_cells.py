"""Tiny stand-ins of the benchmark's cells for the CPU tests: the real
cell's files with every width shrunk, in a scratch checkout root."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from perfbench import registry

REPO = registry.ROOT

#: Widths of the stand-ins, by model_type: (published-key updates, trainer
#: overrides).
SHRINK = {
    "llama": ({"hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "vocab_size": 512},
              {"d_model": 64, "num_heads": 4, "num_kv_heads": 2,
               "head_dim": 16, "d_ff": 128, "vocab_size": 512,
               "attn_q_block": 32, "attn_kv_block": 32, "use_pallas": False}),
    "mamba2": ({"d_model": 64, "vocab_size": 500},
               {"d_model": 64, "vocab_size": 500, "use_pallas": False,
                "ssm": {"d_state": 16, "d_conv": 4, "expand": 2,
                        "head_dim": 16, "chunk": 16}}),
}
SSM = {"d_state": 16, "headdim": 16, "chunk_size": 16}


def make_root(tmp: Path, workload: str, seq_len: int = 64) -> Path:
    """A checkout root holding one tiny cell named ``workload`` (with the
    real cell's traffic, limits and metrics), its file layout the real
    one's."""
    tmp = Path(tmp)
    real = registry.cell(workload, REPO)
    bench = registry.benchmark(REPO)
    pb = tmp / registry.HERE
    for d in ("configs", "traffic", "limits"):
        (pb / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(REPO / registry.HERE / "metrics", pb / "metrics",
                    dirs_exist_ok=True)
    entry = next(c for c in bench["configs"] if c["name"] == real.config_name)
    hf = json.loads((REPO / entry["file"]).read_text())
    published, overrides = SHRINK[hf["model_type"]]
    hf.update(published)
    if "ssm_cfg" in hf:
        hf["ssm_cfg"] = dict(hf["ssm_cfg"], **SSM)
    hf["trainer"] = dict(hf["trainer"], overrides=dict(
        hf["trainer"]["overrides"], **overrides))
    (tmp / entry["file"]).write_text(json.dumps(hf))
    job = dict(real.job, seq_len=seq_len)
    (pb / "traffic" / f"{real.traffic_name}.json").write_text(json.dumps(job))
    shutil.copy(REPO / registry.HERE / "limits" / f"{workload}.json",
                pb / "limits" / f"{workload}.json")
    bench["configs"] = [entry]
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] == workload]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
