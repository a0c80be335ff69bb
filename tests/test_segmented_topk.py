"""Property tests for the segmented multi-shard top-k selection layer
(kernels/ref.local_topk_ref + kernels/ref.segmented_topk_ref, the reduce
behind core.bandit_jax.make_segmented_round_fn's score-policy path).

The exactness claim under test: partition the candidate slots over P
shards *arbitrarily*, take each shard's local top-S in the flat masked-
argmax order, merge the P*S (value, slot) pairs with the lexicographic
reduce (value descending, slot ascending on exact ties) — the result is
bitwise the flat ``top_slots`` over all candidates, for any finite scores
including heavy duplicates and the cold-start BIG sentinel plateaus the
UCB policies produce.  (Any flat pick has fewer than S locally-better
candidates in its own shard, hence sits inside that shard's local top-S.)
"""

import jax
import jax.numpy as jnp
import numpy as np

from _hyp import given, settings, st
from repro.core import bandit_jax
from repro.kernels import ops
from repro.kernels.bandit_round import topk_slots_pallas
from repro.kernels.ref import BIG, local_topk_ref, segmented_topk_ref


def _flat(score, valid, s):
    return np.asarray(bandit_jax.top_slots(jnp.asarray(score),
                                           jnp.asarray(valid), s))


def _segmented(score, valid, owner, p, s):
    """Each shard ranks only its owned+valid slots (global slot ids, the
    make_segmented_round_fn encoding), then the cross-shard reduce."""
    vals, slots = [], []
    for i in range(p):
        own = valid & (owner == i)
        lv, ls = local_topk_ref(
            jnp.asarray(np.where(own, score, -np.inf).astype(np.float32)),
            jnp.asarray(own), s)
        vals.append(np.asarray(lv))
        slots.append(np.asarray(ls))
    return np.asarray(segmented_topk_ref(jnp.asarray(np.stack(vals)),
                                         jnp.asarray(np.stack(slots)), s))


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 8), st.integers(1, 48),
       st.integers(1, 12), st.integers(1, 6))
def test_segmented_equals_flat(seed, p, c, s_round, n_distinct):
    """Random scores drawn from few distinct values (=> massive exact ties),
    ~20% replaced by the cold-start BIG sentinel, random validity, random
    shard ownership: segmented == flat, bitwise."""
    rng = np.random.default_rng(seed)
    score = rng.choice(rng.normal(size=n_distinct).astype(np.float32),
                       size=c)
    score = np.where(rng.random(c) < 0.2, np.float32(BIG), score)
    valid = rng.random(c) < 0.8
    owner = rng.integers(0, p, size=c)
    np.testing.assert_array_equal(
        _segmented(score, valid, owner, p, s_round),
        _flat(score, valid, s_round))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 64), st.integers(1, 10))
def test_top_slots_matches_lax_top_k(seed, c, s_round):
    """The flat masked-argmax order IS lax.top_k's (value descending,
    lowest index on ties) wherever both are defined — the anchor that
    makes the segmented reduce's total order the canonical one."""
    rng = np.random.default_rng(seed)
    score = rng.choice(rng.normal(size=4).astype(np.float32), size=c)
    valid = rng.random(c) < 0.7
    slots = _flat(score, valid, s_round)
    n_valid = int(valid.sum())
    masked = jnp.where(jnp.asarray(valid), jnp.asarray(score), -jnp.inf)
    _, idx = jax.lax.top_k(masked, min(s_round, c))
    take = min(s_round, n_valid)
    np.testing.assert_array_equal(slots[:take], np.asarray(idx)[:take])
    assert (slots[take:] == -1).all()


def test_local_topk_pallas_matches_ref():
    """Interpret-mode Pallas topk_slots kernel == the jnp reference on
    block-boundary shapes and tie-heavy inputs."""
    from repro.kernels.bandit_round import BLOCK
    rng = np.random.default_rng(0)
    for c in (1, 5, BLOCK, BLOCK + 1, 2 * BLOCK + 7):
        score = rng.choice(np.float32([0.0, 1.0, BIG]), size=c)
        valid = rng.random(c) < 0.6
        s = min(c, 7)
        rv, rs = local_topk_ref(jnp.asarray(score), jnp.asarray(valid), s)
        pv, ps = topk_slots_pallas(jnp.asarray(score), jnp.asarray(valid),
                                   s, interpret=True)
        np.testing.assert_array_equal(np.asarray(rs), np.asarray(ps))
        np.testing.assert_array_equal(np.asarray(rv), np.asarray(pv))


def test_ops_local_topk_routes_to_ref_on_cpu():
    score = jnp.asarray(np.float32([3.0, 1.0, 3.0, -2.0]))
    valid = jnp.asarray([True, True, False, True])
    v, s = ops.local_topk(score, valid, 3)
    np.testing.assert_array_equal(np.asarray(s), [0, 1, 3])
    np.testing.assert_array_equal(np.asarray(v), np.float32([3.0, 1.0, -2.0]))


def test_segmented_padding_only_shards():
    """Shards that own nothing contribute all (-inf, -1) rows; the reduce
    must skip them entirely and pad with -1 past exhaustion."""
    vals = jnp.asarray(np.float32([[1.0, -np.inf], [-np.inf, -np.inf]]))
    slots = jnp.asarray(np.int32([[2, -1], [-1, -1]]))
    out = np.asarray(segmented_topk_ref(vals, slots, 3))
    np.testing.assert_array_equal(out, [2, -1, -1])
