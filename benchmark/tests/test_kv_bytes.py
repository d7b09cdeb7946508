"""kv_bytes.py against cases worked by hand."""

import kv_bytes
import pytest

PHI3 = {"n_layers": 32, "n_heads": 32, "n_kv_heads": 32, "head_dim": 96, "dtype_bytes": 2, "window": 2047}
MISTRAL = {"n_layers": 32, "n_heads": 32, "n_kv_heads": 8, "head_dim": 128, "dtype_bytes": 2, "window": 4096}
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_bytes_per_token_are_the_published_arithmetic():
    assert kv_bytes.kv_bytes_per_token(PHI3) == 393216  # 2 * 32 * 32 * 96 * 2
    assert kv_bytes.kv_bytes_per_token(MISTRAL) == 131072  # 2 * 32 * 8 * 128 * 2
    assert kv_bytes.kv_bytes_per_token(MISTRAL, chips=4) == 32768


def test_decode_token_reads_its_context_once_capped_by_the_window():
    nbytes, flops = kv_bytes.decode_token(1000, PHI3)
    assert nbytes == 1000 * 393216
    assert flops == 4 * 1000 * 32 * 96 * 32  # QK^T and PV, 2 flops a multiply-add
    assert kv_bytes.decode_token(3000, PHI3)[0] == 2047 * 393216
    assert kv_bytes.decode_token(1000, MISTRAL, chips=4)[0] == 1000 * 32768


def test_prefill_reads_each_position_once_and_computes_the_causal_half():
    nbytes, flops = kv_bytes.prefill(4, MISTRAL)
    assert nbytes == 4 * 131072
    assert flops == 4 * (1 + 2 + 3 + 4) * 32 * 128 * 32


def test_roofline_takes_the_larger_bound_per_call_and_names_it():
    decode = kv_bytes.decode_token(1000, PHI3)  # 1 flop a byte: memory-bound
    secs, bound = kv_bytes.min_seconds([decode], V5E)
    assert bound == "memory" and secs == pytest.approx(1000 * 393216 / 819e9)
    pre = kv_bytes.prefill(2048, PHI3)  # ~1000 flops a byte: compute-bound
    secs, bound = kv_bytes.min_seconds([pre], V5E)
    assert bound == "compute" and secs == pytest.approx(pre[1] / 197e12)


def test_roofline_reader_counts_what_was_generated_in_the_traced_interval():
    """Two streams in lockstep; the capture covers the second half of the
    stretch that made their second events: half of those events' work counts."""
    import time

    import kv_roofline
    import loadgen

    now = time.monotonic()
    recs = []
    for _ in range(2):
        spec = loadgen.Spec(100, 64, "x" * 99)
        recs.append(loadgen.Record(spec, now, now, [(now + 10, "a" * 32), (now + 20, "b" * 32)],
                                   now + 20, 64))
    wall = time.time() - time.monotonic()
    ctx = {"trace": {"window_s": 5.0, "busy_s": 5.0,
                     "ops": {"closed_call.1 custom-call:tpu_custom_call": 2.0}},
           "profile": {"header": {"ts": wall + now + 15, "duration_s": 5.0}},
           "peaks": {"TPU v5 lite": V5E}, "device": {"kind": "TPU v5 lite"},
           "config": {"kv": PHI3}, "cell": {"chips": 1}, "records": recs}
    got = kv_roofline.read(ctx, {"pattern": "tpu_custom_call"})
    one = sum(kv_bytes.decode_token(132 + i, PHI3)[0] for i in range(32)) / 819e9
    assert got == pytest.approx(100.0 * (2 * 0.5 * one) / 2.0, rel=1e-3)
