"""Operations and bytes one bandit-round needs, counted from the
algorithm (paper Sect. III, Algorithm 1), not from the layout a kernel
chose: a selection reads each polled candidate's statistic columns and
the candidate list, whatever implements it.  All values are 4 bytes
(float32 or int32)."""

from __future__ import annotations

WORD = 4
# statistic columns each policy's score reads per candidate
COLUMNS = {"fedcs": 2, "extended_fedcs": 3, "naive_ucb": 2,
           "elementwise_ucb": 3, "random": 1, "oracle": 2,
           "discounted_ucb": 3, "sliding_ucb": 4}
# operations per candidate that turn the columns into scores
SCORE_OPS = {"fedcs": 0, "extended_fedcs": 2, "naive_ucb": 7,
             "elementwise_ucb": 9, "random": 0, "oracle": 0,
             "discounted_ucb": 12, "sliding_ucb": 11}
# policies that rank a fixed score (top-S); the others run Algorithm 1
SCORE_POLICIES = ("naive_ucb", "random")
GREEDY_STEP_OPS = 7     # T_inc estimate and the arg-min, per candidate
TOP_STEP_OPS = 2        # compare and select, per candidate
EQ8_OPS = 40            # two inverse-CDF draws, clip, two divides
DRAW_OPS = 20           # one uniform draw per candidate
STATE_FIELDS = 12       # per-client statistics ``observe`` updates


def selection(policy: str, c: int, s: int) -> tuple[int, int]:
    """(ops, bytes) of selecting ``s`` of ``c`` polled candidates."""
    step = TOP_STEP_OPS if policy in SCORE_POLICIES else GREEDY_STEP_OPS
    ops = c * (SCORE_OPS[policy] + s * step)
    nbytes = c * (COLUMNS[policy] + 1) * WORD + s * WORD
    return ops, nbytes


def round_work(policy: str, c: int, s: int, cells: int) -> tuple[int, int]:
    """(ops, bytes) of one whole bandit-round: the candidate draw, Eq. 8
    times at the candidates (three means each), the cell factor, the
    selection, the realised schedule and the ``observe`` update."""
    ops, nbytes = selection(policy, c, s)
    ops += c * (DRAW_OPS + EQ8_OPS) + cells * DRAW_OPS + c
    nbytes += c * 3 * WORD + cells * WORD
    ops += s * 10
    nbytes += s * STATE_FIELDS * 2 * WORD
    return ops, nbytes
