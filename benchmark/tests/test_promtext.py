import promtext

A = """# HELP bee2bee_engine_ttft_ms time to first token
# TYPE bee2bee_engine_ttft_ms histogram
bee2bee_engine_ttft_ms_bucket{le="1024"} 1
bee2bee_engine_ttft_ms_sum 800.5
bee2bee_engine_ttft_ms_count 1
bee2bee_engine_compiles_total{root="decode"} 2
bee2bee_engine_compiles_total{root="prefill"} 1
bee2bee_engine_paged_blocks_in_use 3
"""
B = A.replace("800.5", "2000.5").replace("_count 1", "_count 4") \
     .replace('root="decode"} 2', 'root="decode"} 5') \
     + 'bee2bee_engine_compiles_total{root="other"} 1\n'


def test_parse_keeps_label_sets_apart_and_skips_comments():
    s = promtext.parse(A)
    assert s['bee2bee_engine_compiles_total{root="decode"}'] == 2
    assert promtext.total(s, "bee2bee_engine_compiles_total") == 3
    assert promtext.total(s, "bee2bee_engine_ttft_ms") is None  # only _sum/_count/_bucket exist
    assert promtext.total(s, "no_such_metric") is None


def test_delta_sums_label_sets_and_counts_a_new_series_from_zero():
    a, b = promtext.parse(A), promtext.parse(B)
    assert promtext.delta(a, b, "bee2bee_engine_compiles_total") == 4  # +3 decode, +1 new root
    assert promtext.delta(a, b, "no_such_metric") is None


def test_delta_mean_is_sum_over_count_and_none_without_observations():
    a, b = promtext.parse(A), promtext.parse(B)
    assert promtext.delta_mean(a, b, "bee2bee_engine_ttft_ms") == (2000.5 - 800.5) / 3
    assert promtext.delta_mean(a, a, "bee2bee_engine_ttft_ms") is None
