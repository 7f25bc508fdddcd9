"""Census-derived necessary bytes and FLOPs, checked against counts
worked out by hand, and the peaks table."""
import pytest

from yardstick import counts
from yardstick.peaks import peaks

D = 785
CENSUS = {"messages": 10, "broadcasts": 2, "client_steps": 40,
          "ops": {"deliver_rows": 8, "bucket_applies": 2,
                  "ring_scatters": 3}}


def test_client_steps_sums_completed_rounds():
    # sizes 16, 18, 20: a client with 2 rounds took 34 steps, one with
    # 3 took 54, one with none took 0
    assert counts.client_steps([2, 3, 0], [16, 18, 20]) == 34 + 54


def test_tick_kernel_bytes_by_hand():
    # 3*8 + 3*2 + 2*10 + 2*3 = 56 rows without DP; 5*10 with DP: 86
    assert counts.tick_kernel_bytes(CENSUS, D, dp=False) == 56 * D * 4
    assert counts.tick_kernel_bytes(CENSUS, D, dp=True) == 86 * D * 4


def test_dp_kernel_bytes_by_hand():
    assert counts.dp_kernel_bytes(CENSUS, D) == 30 * D * 4


def test_round_work_by_hand():
    work = {"step_flops": 6.0 * D, "step_rows": 1,
            "eval_flops": 2 * 2.0 * 100 * D, "eval_rows": 2 * 100}
    w = counts.round_work(CENSUS, D, dp=True, work=work, evals=3)
    # FLOPs: 6 D per step + 2 passes x 2 N D per eval
    assert w["flops"] == 6 * D * 40 + 3 * 2 * 2 * 100 * D
    # rows: 40 samples + 4 x 10 (w, U) + 2 broadcasts + 3 x 2 x 100 eval
    rows = 40 + 40 + 2 + 600
    assert w["bytes"] == rows * D * 4 + 86 * D * 4 + 30 * D * 4


def test_peaks_keyed_by_device_kind():
    pk = peaks("TPU v5 lite")
    assert pk["hbm_bytes_per_s"] == 819e9 and pk["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
