"""The benchmark's FLOP and byte counts against hand counts at a tiny
size, the head and a truncated depth included, and every configuration
and traffic file's counts against those recorded before the
architectures and kernel families moved into files of their own."""
import json
from pathlib import Path

import pytest

from perfbench import counts

LLAMA = {"model_type": "llama", "hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 512}
MAMBA = {"model_type": "mamba2", "hidden_size": 64, "num_hidden_layers": 4,
         "vocab_size": 500,
         "ssm_cfg": {"d_state": 16, "d_conv": 4, "expand": 2, "headdim": 16,
                     "ngroups": 1, "chunk_size": 4}}
JOB = {"batch": 2, "seq_len": 8}
TOKENS = 16


def llama_layer_fwd():
    # per token: wq 64x64, wk 64x32, wv 64x32, wo 64x64, wg/wu/wd 64x128
    mats = 64 * 64 + 64 * 32 + 64 * 32 + 64 * 64 + 3 * 64 * 128
    # causal pairs of 8 positions: 36; QK^T and PV, 2 flops a multiply-add,
    # 4 heads of 16, batch 2
    attn = 2 * 2 * 36 * 16 * 4 * 2
    return 2 * TOKENS * mats + attn


def test_llama_step_flops_full_and_truncated():
    fwd = llama_layer_fwd()
    head = 2 * TOKENS * 64 * 512
    assert counts.step_flops(LLAMA, JOB, None) == 6 * fwd + 3 * head
    assert counts.step_flops(LLAMA, JOB, 2) == 6 * fwd + 3 * head
    # depth 1: layer 0 forward only; layer 1 forward and backward but no
    # gradient of its own input through wq/wk/wv
    entry = 2 * TOKENS * (64 * 64 + 2 * 64 * 32)
    assert counts.step_flops(LLAMA, JOB, 1) == 4 * fwd + 3 * head - entry
    assert counts.step_flops(LLAMA, JOB, None) == 10334208


def test_mamba_step_flops():
    d_in, nh, n, p, q = 128, 8, 16, 16, 4
    proj = 64 * (2 * d_in + 2 * n + nh) + d_in * 64
    conv = 2 * TOKENS * 4 * (d_in + 2 * n)
    # per chunk and head: C B^T (q*q*n), (L.S) X (q*q*p), C state and
    # the state update (q*n*p each); 2 flops a multiply-add
    ssd = (TOKENS // q) * nh * 2 * (q * q * n + q * q * p + 2 * q * n * p)
    fwd = 2 * TOKENS * proj + conv + ssd
    head = 2 * TOKENS * 64 * 500
    full = 4 * fwd + 3 * head + 4 * 2 * fwd
    assert counts.step_flops(MAMBA, JOB, None) == full
    entry = 2 * TOKENS * 64 * (2 * d_in + 2 * n + nh)
    assert counts.step_flops(MAMBA, JOB, 1) == 4 * fwd + 3 * head \
        + 2 * fwd - entry


def test_flash_kernel_costs():
    mm = 2 * 2 * 4 * 16 * 36          # one matmul over the causal pairs
    q, kv, rows = 2 * 4 * 8 * 16 * 2, 2 * 2 * 8 * 16 * 2, 2 * 4 * 8 * 4
    assert counts.kernel_cost(LLAMA, JOB, "flash_fwd_lse") == {
        "flops": 2 * mm, "bytes": 2 * q + 2 * kv + rows}
    assert counts.kernel_cost(LLAMA, JOB, "flash_fwd")["bytes"] == \
        2 * q + 2 * kv
    assert counts.kernel_cost(LLAMA, JOB, "flash_dq")["flops"] == 3 * mm
    assert counts.kernel_cost(LLAMA, JOB, "flash_dkv")["flops"] == 4 * mm
    assert counts.kernel_cost(LLAMA, JOB, "flash_delta")["bytes"] == \
        2 * q + rows


def test_ssd_kernel_costs():
    bh, chunks, q, n, p, s = 2 * 8, 2, 4, 16, 16, 8
    fwd = counts.kernel_cost(MAMBA, JOB, "ssd_fwd")
    assert fwd["flops"] == bh * chunks * 2 * (q * q * (n + p) + 2 * q * n * p)
    ins = bh * s * p * 2 + 2 * bh * s * n * 2 + bh * s * 4
    assert fwd["bytes"] == ins + bh * s * p * 4 + bh * p * n * 4
    st = counts.kernel_cost(MAMBA, JOB, "ssd_fwd_states")
    assert st["bytes"] - fwd["bytes"] == bh * chunks * p * n * 4
    with pytest.raises(ValueError):
        counts.kernel_cost(MAMBA, JOB, "ssd_nothing")


def test_flash_cost_at_the_call_shape():
    # 8 rows in 2 microbatches over 2 data shards: 2 rows a call; 2-way
    # tensor parallel: 2 of the 4 query heads and 1 of the 2 kv heads
    job = dict(JOB, batch=8, microbatches=2,
               mesh={"stage": 2, "data": 2, "model": 2})
    assert counts.call_shape(job) == {"rows": 2, "model": 2}
    mm = 2 * 2 * 2 * 16 * 36
    q, kv, rows = 2 * 2 * 8 * 16 * 2, 2 * 1 * 8 * 16 * 2, 2 * 2 * 8 * 4
    assert counts.kernel_cost(LLAMA, job, "flash_fwd_lse") == {
        "flops": 2 * mm, "bytes": 2 * q + 2 * kv + rows}
    assert counts.kernel_cost(LLAMA, job, "flash_dkv")["bytes"] == \
        2 * q + 4 * kv + 2 * rows
    # one device and one microbatch: the job's whole batch and heads
    one = dict(JOB, microbatches=1, mesh={"stage": 1, "data": 1, "model": 1})
    for kind in ("flash_fwd", "flash_fwd_lse", "flash_delta", "flash_dq",
                 "flash_dkv"):
        assert counts.kernel_cost(LLAMA, one, kind) == \
            counts.kernel_cost(LLAMA, JOB, kind)


READINGS = json.loads((Path(__file__).resolve().parent / "data"
                       / "readings.json").read_text())
REPO = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("pair", sorted(READINGS["counts"]))
def test_counts_unchanged(pair):
    """Every configuration file under every traffic file: the step's flops
    at every depth and each kind's call cost equal the recorded ones."""
    from perfbench import registry
    config, traffic = pair.split("|")
    hf = registry.canonical(json.loads(
        (REPO / "perfbench/configs" / f"{config}.json").read_text()))
    job = json.loads(
        (REPO / "perfbench/traffic" / f"{traffic}.json").read_text())
    want = READINGS["counts"][pair]
    assert {d: counts.step_flops(hf, job, None if d == "None" else int(d))
            for d in want["step_flops"]} == want["step_flops"]
    assert len(want["step_flops"]) == hf["num_hidden_layers"] + 1
    assert want["kernel_cost"]
    assert {k: counts.kernel_cost(hf, job, k)
            for k in want["kernel_cost"]} == want["kernel_cost"]
