"""The needed-work counter at qwen2.5 widths, read through the qwen2
family (``bench/families/qwen2.py``)."""

import pytest

from bench import work
from bench.spec import load_model

T = load_model("qwen2.5-3b")
D = load_model("qwen2.5-0.5b")


def test_parameter_counts_match_the_published_models():
    assert T.params() == 3_085_938_688          # "3.09B" on the model card
    assert D.params() == 494_032_768            # "0.49B"
    assert T.kv_bytes(1) == 36 * 2 * 2 * 128 * 2
    assert D.kv_bytes(1) == 24 * 2 * 2 * 64 * 2


@pytest.mark.parametrize("gamma,flops,nbytes", [
    (0, 19_557_285_888, 6_301_782_016),
    (1, 42_382_577_664, 7_333_167_104),
    (12, 293_460_787_200, 18_678_403_072),
])
def test_pass_counts_are_the_parents(gamma, flops, nbytes):
    """The counts the benchmark gave before architectures moved into
    family files, exactly."""
    w = work.pass_work(T, D, gamma, [1500, 2000, 37])
    assert (w.flops, w.bytes, w.passes) == (flops, nbytes, 1)


def test_fused_pass_counts_the_target_alone():
    ctx = [1500, 2000]
    w = work.pass_work(T, D, 0, ctx)
    mm = T.matmul_params()
    attn = sum(4 * 36 * 16 * 128 * c for c in ctx)
    assert w.flops == pytest.approx(2 * mm * 2 + attn)
    assert w.bytes == pytest.approx(2 * mm + sum(ctx) * 36 * 2 * 2 * 128 * 2)
    assert w.passes == 1


def test_a_window_adds_draft_work_per_decided_token():
    ctx = [1000] * 4
    fused = work.pass_work(T, D, 0, ctx)
    win = work.pass_work(T, D, 3, ctx)
    draft_mm = D.matmul_params()
    extra_target = 3 * (2 * T.matmul_params() * 4
                        + sum(4 * 36 * 16 * 128 * c for c in ctx))
    extra_draft = 3 * (2 * draft_mm * 4 + sum(4 * 24 * 14 * 64 * c
                                              for c in ctx))
    assert win.flops - fused.flops == pytest.approx(extra_target + extra_draft)
    assert win.bytes - fused.bytes == pytest.approx(
        3 * (2 * draft_mm + sum(ctx) * D.kv_bytes(1)))


def test_a_fused_pass_is_bound_by_weight_bytes():
    """About 6 GB of target weights a pass: at 819 GB/s no pass can take
    under 7.5 ms on a v5e, and it is memory-bound there."""
    w = work.pass_work(T, D, 0, [2600] * 28)
    assert w.bytes / 819e9 > w.flops / 197e12
    assert 7.5e-3 < w.bytes / 819e9 < 1.5e-2


def test_chunks_sum_their_passes():
    class C:
        passes, gammas, contexts = 3, [0, 2, 0, 5], [100, 200]
    total = work.chunks_work(T, D, [C(), C()])
    one = sum((work.pass_work(T, D, g, [100, 200]) for g in (0, 2, 0)),
              start=work.Work())
    assert total.passes == 6
    assert total.flops == pytest.approx(2 * one.flops)
