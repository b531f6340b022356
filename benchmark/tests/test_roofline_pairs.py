import pytest

from lib.peaks import peaks
from lib.roofline import least_seconds
from lib.roofline_pairs import pair_ops_bytes
from readers import roofline_pairs


def test_bytes_of_the_full_pool_assignment_by_hand():
    # a_pad = 262144 rows x k = 64 candidates, int32, + one partner a row
    ops, nbytes = pair_ops_bytes(262144, 64)
    assert ops == 262144 * 64 == 16_777_216
    assert nbytes == 16_777_216 * 4 + 262144 * 4 == 68_157_440
    least, roof = least_seconds(ops, nbytes, peaks("TPU v5 lite"))
    assert roof == "memory"
    assert least == pytest.approx(68_157_440 / 819e9)  # 83.2 us


class _Ctx:
    def __init__(self, kernel, programs):
        self.trace = {"programs": programs} if programs is not None else None
        self.window_crumbs = [{"kernel": kernel}] if kernel else []
        self.device = {"kind": "TPU v5 lite"}
        self.notes = {}


def test_reader_reads_the_first_run_and_nothing_where_nothing_paired():
    args = {"program": "pair_partners"}
    kernel = {"kernel": "topk_candidates_big+pair_partners",
              "a_pad": 262144, "k": 64}
    runs = [("jit_pair_partners(1)", 2.0, 2.5),
            ("jit_pair_partners(1)", 6.0, 6.001),
            ("jit_topk_candidates_big(2)", 1.0, 2.0)]
    ctx = _Ctx(kernel, runs)
    share = roofline_pairs.read(ctx, args)
    assert share == pytest.approx(100 * (68_157_440 / 819e9) / 0.5)
    assert ctx.notes["roofline"]["pair_partners"]["device_s"] == 0.5
    # another variant dispatched, no such program in the trace, no
    # trace, no crumb: nothing to read, and no raise
    other = dict(kernel, kernel="topk_candidates_big")
    assert roofline_pairs.read(_Ctx(other, runs), args) is None
    assert roofline_pairs.read(_Ctx(kernel, runs[2:]), args) is None
    assert roofline_pairs.read(_Ctx(kernel, None), args) is None
    assert roofline_pairs.read(_Ctx(None, runs), args) is None
