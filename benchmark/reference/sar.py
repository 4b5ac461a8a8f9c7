"""The plain reference of SAR (Smart Adaptive Recommendations), and the
seeded weights a SAR cell serves.

The published description (SAR.scala / SARModel.scala of MMLSpark; the
same algorithm as `recommenders`' SARSingleNode): a user's affinity to an
item is the sum of that user's ratings of it (time decay off here); two
items' similarity is the Jaccard index of the sets of users who rated
them, kept where at least `support_threshold` users rated both; a user's
score for an item is affinity row x similarity column; the recommendations
are the `k` best-scored items the user has not rated, best first.

Nothing here imports the program or takes anything the program has made.
`weights` makes, on the device and from the seed, the state a fitted model
holds; `verify` and `control_answer` compare what the timed calls returned
for a seeded sample of users, scoring them in float32 at the highest
matmul precision."""

from __future__ import annotations

from functools import partial

import numpy as np

ROW_BLOCK = 1024          # rows made at a time: bounds the maker's own memory


def weights(key, users: int, items: int, interactions: int,
            support_threshold: int) -> dict:
    """-> {"affinity" f32 (U, I), "seen" bool (U, I), "similarity" f32
    (I, I)}, device arrays: a stand-in at a MovieLens shape. User u has
    rated item i with probability min(1, interactions * a_u * q_i): a_u a
    log-normal activity (mean over median about 2, as MovieLens-10M's 143
    over 69), q_i a Zipf-like popularity (the most popular item in about
    half of the histories); ratings in half stars. Every row depends on
    the key and its own index alone.

    Rows are made `ROW_BLOCK` at a time into buffers updated in place, and
    the co-occurrence counts (products of 0/1 values, exact in bfloat16
    with float32 accumulation below 2**24) become the similarity in place,
    so that the maker never holds much more than what it returns."""
    import jax
    import jax.numpy as jnp

    block = min(ROW_BLOCK, users)
    k_act, k_pop, k_rows = jax.random.split(key, 3)

    @jax.jit
    def make(k_act, k_pop, k_rows):
        activity = jnp.exp(1.2 * jax.random.normal(k_act, (users,)))
        activity = activity / activity.sum()
        popularity = 1.0 / (jax.random.permutation(k_pop, items) + 50.0)
        popularity = popularity / popularity.sum()

        def one_row(row):
            bits = jax.random.bits(jax.random.fold_in(k_rows, row), (items,),
                                   jnp.uint32)
            uniform = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
            rated = uniform < interactions * activity[row] * popularity
            stars = 0.5 * ((bits & 0xFF) % 10 + 1).astype(jnp.float32)
            return jnp.where(rated, stars, 0.0), rated

        def body(b, carry):
            affinity, seen, both = carry
            start = jnp.minimum(b * block, users - block)
            rows = start + jnp.arange(block)
            aff_rows, seen_rows = jax.vmap(one_row)(rows)
            # the last block reaches back over rows already made (the same
            # values again): they are counted once
            fresh = (rows >= b * block)[:, None] & seen_rows
            f = fresh.astype(jnp.bfloat16)
            both = both + jnp.dot(f.T, f, preferred_element_type=jnp.float32)
            affinity = jax.lax.dynamic_update_slice(affinity, aff_rows,
                                                    (start, 0))
            seen = jax.lax.dynamic_update_slice(seen, seen_rows, (start, 0))
            return affinity, seen, both

        return jax.lax.fori_loop(
            0, -(-users // block), body,
            (jnp.zeros((users, items), jnp.float32),
             jnp.zeros((users, items), jnp.bool_),
             jnp.zeros((items, items), jnp.float32)))

    @partial(jax.jit, donate_argnums=0)
    def jaccard(both):
        alone = jnp.diagonal(both)
        either = alone[:, None] + alone[None, :] - both
        sim = jnp.where(either > 0, both / jnp.maximum(either, 1.0), 0.0)
        return jnp.where(both >= support_threshold, sim, 0.0)

    affinity, seen, both = make(k_act, k_pop, k_rows)
    return {"affinity": affinity, "seen": seen, "similarity": jaccard(both)}


def sample_users(rng, w: dict, n: int) -> np.ndarray:
    """`n` users drawn from the seed, the one with the longest history
    among them."""
    users = w["seen"].shape[0]
    drawn = rng.choice(users, size=min(n, users), replace=False)
    drawn[0] = int(np.asarray(w["seen"].sum(axis=1).argmax()))
    return np.unique(drawn)


def _through(x, precision):
    """`x` rounded through a lower precision, back in float32."""
    import jax.numpy as jnp

    if precision is None:
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "int8":        # symmetric, one scale for the tensor
        scale = jnp.abs(x).max() / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    raise ValueError(f"no such precision: {precision!r}")


def scores(w: dict, sample: np.ndarray, through=None) -> np.ndarray:
    """Scores of the sampled users for every item, float32 at the highest
    matmul precision, seen items at -inf. `through` rounds both factors
    through a lower precision first (the control)."""
    import jax
    import jax.numpy as jnp

    rows = jnp.asarray(sample)
    out = jnp.dot(_through(w["affinity"][rows], through),
                  _through(w["similarity"], through),
                  precision=jax.lax.Precision.HIGHEST)
    return np.asarray(jnp.where(w["seen"][rows], -jnp.inf, out), np.float64)


def top_k(masked: np.ndarray, k: int):
    """-> (items (n, k), values (n, k)), best first."""
    part = np.argpartition(-masked, k - 1, axis=1)[:, :k]
    vals = np.take_along_axis(masked, part, axis=1)
    order = np.argsort(-vals, axis=1, kind="stable")
    return (np.take_along_axis(part, order, axis=1),
            np.take_along_axis(vals, order, axis=1))


def verify(w: dict, sample: np.ndarray, items_out: np.ndarray,
           ratings_out: np.ndarray, k: int) -> dict:
    """What the program returned (`items_out`, `ratings_out`: (U, k), every
    user) against the reference.

    rating_gap_p90: over the sampled users' k recommendations, the gap
        between the rating returned and the reference's score of that
        item, against the reference's k-th best score of that user (or
        the median user's, whichever is larger); the value nine in ten
        stay within. Precision of the scoring product.
    topk_regret: rank by rank, how far the reference's score of the item
        returned lies below the reference's score of its own item at that
        rank, on the same scale; the worst of the sample. A selection or
        an order that is not the best one.
    seen_or_invalid: over EVERY user, recommendations that are no item or
        one the user has rated; a user with fewer than k unrated items
        gets no item (-1) at the ranks past them, and only there."""
    import jax.numpy as jnp

    users, items = w["seen"].shape
    valid = (items_out >= 0) & (items_out < items)
    clipped = np.where(valid, items_out, 0)
    rated = np.asarray(
        w["seen"][jnp.arange(users)[:, None], jnp.asarray(clipped)])
    unrated = items - np.asarray(w["seen"].sum(axis=1))
    due = np.arange(k)[None, :] < unrated[:, None]
    seen_or_invalid = int(np.where(due, ~valid | rated,
                                   items_out != -1).sum())

    ref = scores(w, sample)
    _best_items, best = top_k(ref, k)
    best = np.where(np.isfinite(best), best, 0.0)     # ranks not due
    scale = np.maximum(best[:, -1], np.median(best[:, -1]))[:, None]
    raw = np.take_along_axis(ref, clipped[sample], axis=1)
    of_returned = np.where(np.isfinite(raw) & due[sample], raw, 0.0)
    gap = np.abs(ratings_out[sample] - of_returned) / scale
    regret = np.maximum(best - of_returned, 0.0) / scale
    return {"rating_gap_p90": float(np.quantile(gap, 0.9)),
            "topk_regret": float(regret.max()),
            "seen_or_invalid": seen_or_invalid}


def control_answer(w: dict, sample: np.ndarray, items_out, ratings_out,
                   k: int, through: str) -> tuple:
    """The reference computed through the precision `through`, put in the
    program's place for the sampled users."""
    low_items, low_vals = top_k(scores(w, sample, through=through), k)
    low_items = np.where(np.isfinite(low_vals), low_items, -1)
    low_vals = np.where(np.isfinite(low_vals), low_vals, 0.0)
    items_out, ratings_out = items_out.copy(), ratings_out.copy()
    items_out[sample] = low_items
    ratings_out[sample] = low_vals
    return items_out, ratings_out
