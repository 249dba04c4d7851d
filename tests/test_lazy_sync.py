"""LazySync correctness: the speculative grouped-embedding protocol must be
EXACTLY equivalent to dense synchronous SGD at commit boundaries, and
conflict detection must have no false negatives (Bloom property)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.lazy_sync import LazyEmbed, LazySyncConfig, init_state


@pytest.fixture()
def setup():
    mcfg = get_smoke_config("qwen3_4b")
    cfg = LazySyncConfig(num_groups=4, commit_interval=4,
                         max_reconcile_rows=128, embed_lr=0.1)
    emb = LazyEmbed(mcfg, cfg)
    params = emb.init(jax.random.key(0))
    state = init_state(cfg, mcfg.vocab)
    return mcfg, cfg, emb, params, state


def _rand_touch_grads(mcfg, cfg, key, t=16):
    k1, k2 = jax.random.split(key)
    touched = jax.random.randint(k1, (cfg.num_groups, t), 0, mcfg.vocab,
                                 dtype=jnp.int32)
    g = jax.random.normal(k2, (cfg.num_groups, t, mcfg.d_model), jnp.float32) * 0.1
    grads = jnp.zeros((cfg.num_groups, mcfg.vocab, mcfg.d_model), jnp.float32)
    grads = grads.at[jnp.arange(cfg.num_groups)[:, None], touched].add(g)
    return touched, grads


def test_commit_equals_dense_sgd(setup):
    """After a full commit, the table equals dense synchronous SGD on the
    summed gradients (the linear-update exactness argument)."""
    mcfg, cfg, emb, params, state = setup
    dense = params["base"].astype(jnp.float32)
    key = jax.random.key(1)
    for step in range(cfg.commit_interval):
        key, k = jax.random.split(key)
        touched, grads = _rand_touch_grads(mcfg, cfg, k)
        dense = dense - cfg.embed_lr * jnp.sum(grads, axis=0)
        params, state, _ = emb.sync_step(params, state, touched, grads)
    # step K-1 triggered the commit
    np.testing.assert_allclose(
        np.asarray(params["base"], np.float32), np.asarray(dense, np.float32),
        rtol=2e-2, atol=2e-2)
    for g in range(cfg.num_groups):
        np.testing.assert_allclose(
            np.asarray(params["table"][g], np.float32),
            np.asarray(dense, np.float32), rtol=2e-2, atol=2e-2)


def test_conflict_no_false_negatives(setup):
    """Rows touched by two groups MUST be detected (Bloom: no false negs)."""
    mcfg, cfg, emb, params, state = setup
    shared_row = 7
    touched = jnp.stack([
        jnp.full((8,), shared_row, jnp.int32),
        jnp.full((8,), shared_row, jnp.int32),
        jnp.arange(100, 108, dtype=jnp.int32),
        jnp.arange(200, 208, dtype=jnp.int32),
    ])
    sigs = emb.signatures(touched)
    rows, valid = emb.detect_conflicts(touched, sigs)
    hit = bool(jnp.any((rows == shared_row) & valid))
    assert hit


def test_reconciled_row_exact(setup):
    """A conflicting row must be exactly merged across groups immediately."""
    mcfg, cfg, emb, params, state = setup
    row = 3
    touched = jnp.full((cfg.num_groups, 4), row, jnp.int32)
    grads = jnp.zeros((cfg.num_groups, mcfg.vocab, mcfg.d_model), jnp.float32)
    deltas = jnp.arange(1, cfg.num_groups + 1, dtype=jnp.float32)
    for g in range(cfg.num_groups):
        grads = grads.at[g, row].set(deltas[g])
    expect = (params["base"][row].astype(jnp.float32)
              - cfg.embed_lr * jnp.sum(deltas) * jnp.ones((mcfg.d_model,)))
    params, state, m = emb.sync_step(params, state, touched, grads)
    assert int(m["lazy_conflict_rows"]) >= 1
    np.testing.assert_allclose(np.asarray(params["base"][row], np.float32),
                               np.asarray(expect), rtol=2e-2, atol=2e-2)


def test_pinned_row_forced_into_reconcile(setup):
    """§5.5 pin rule: a row whose conflict streak reached pin_streak must be
    eagerly reconciled even when only ONE group touches it (no signature
    conflict fires).  Regression: the seed computed `pinned` but never used
    it."""
    mcfg, cfg, emb, params, state = setup
    row = 11
    touched = jnp.stack([
        jnp.full((8,), row, jnp.int32),            # only group 0 touches it
        jnp.arange(100, 108, dtype=jnp.int32),
        jnp.arange(200, 208, dtype=jnp.int32),
        jnp.arange(300, 308, dtype=jnp.int32),
    ])
    state = {**state, "streak": state["streak"].at[row].set(cfg.pin_streak)}
    grads = jnp.zeros((cfg.num_groups, mcfg.vocab, mcfg.d_model), jnp.float32)
    grads = grads.at[0, row].set(1.0)
    expect = (params["base"][row].astype(jnp.float32)
              - cfg.embed_lr * jnp.ones((mcfg.d_model,)))
    params2, state2, m = emb.sync_step(params, state, touched, grads)
    assert int(m["lazy_pinned"]) >= 1
    # eager sync: the committed base must already include group 0's update
    np.testing.assert_allclose(np.asarray(params2["base"][row], np.float32),
                               np.asarray(expect), rtol=2e-2, atol=2e-2)


def test_unpinned_single_writer_stays_lazy(setup):
    """Counterpart: with no streak, a single-writer row must NOT be eagerly
    committed to base (it stays speculative until conflict/commit)."""
    mcfg, cfg, emb, params, state = setup
    row = 11
    touched = jnp.stack([
        jnp.full((8,), row, jnp.int32),
        jnp.arange(100, 108, dtype=jnp.int32),
        jnp.arange(200, 208, dtype=jnp.int32),
        jnp.arange(300, 308, dtype=jnp.int32),
    ])
    grads = jnp.zeros((cfg.num_groups, mcfg.vocab, mcfg.d_model), jnp.float32)
    grads = grads.at[0, row].set(1.0)
    base_before = np.asarray(params["base"][row], np.float32)
    params2, _, m = emb.sync_step(params, state, touched, grads)
    assert int(m["lazy_pinned"]) == 0
    np.testing.assert_allclose(np.asarray(params2["base"][row], np.float32),
                               base_before, rtol=1e-6, atol=1e-6)


def test_streak_counts_steps_not_duplicates(setup):
    """A row appearing many times in one step's touched list must gain
    streak +1 per step, not +k (scatter-add over duplicates would pin hot
    rows after one step and wrap int8 at 256 touches)."""
    mcfg, cfg, emb, params, state = setup
    row = 7
    # 2 groups each touch `row` 8 times -> conflict, 16 duplicate entries
    touched = jnp.stack([
        jnp.full((8,), row, jnp.int32),
        jnp.full((8,), row, jnp.int32),
        jnp.arange(100, 108, dtype=jnp.int32),
        jnp.arange(200, 208, dtype=jnp.int32),
    ])
    grads = jnp.zeros((cfg.num_groups, mcfg.vocab, mcfg.d_model), jnp.float32)
    for step in range(2):
        params, state, m = emb.sync_step(params, state, touched, grads)
        assert int(state["streak"][row]) == step + 1, (
            step, int(state["streak"][row]))


def test_streak_resets_on_nonconflicting_touch(setup):
    """The streak is a CONSECUTIVE-conflict count: a touched-but-clean step
    zeroes it, so rows conflicting on alternating steps never pin."""
    mcfg, cfg, emb, params, state = setup
    row = 7
    conflicting = jnp.stack([
        jnp.full((8,), row, jnp.int32),
        jnp.full((8,), row, jnp.int32),
        jnp.arange(100, 108, dtype=jnp.int32),
        jnp.arange(200, 208, dtype=jnp.int32),
    ])
    solo = jnp.stack([
        jnp.full((8,), row, jnp.int32),            # only group 0 touches it
        jnp.arange(300, 308, dtype=jnp.int32),
        jnp.arange(100, 108, dtype=jnp.int32),
        jnp.arange(200, 208, dtype=jnp.int32),
    ])
    grads = jnp.zeros((cfg.num_groups, mcfg.vocab, mcfg.d_model), jnp.float32)
    params, state, _ = emb.sync_step(params, state, conflicting, grads)
    assert int(state["streak"][row]) == 1
    params, state, _ = emb.sync_step(params, state, solo, grads)
    assert int(state["streak"][row]) == 0  # clean touch resets
    params, state, _ = emb.sync_step(params, state, conflicting, grads)
    assert int(state["streak"][row]) == 1  # starts over


def test_pinned_row_survives_budget_pressure(setup):
    """Pinned entries outrank ordinary conflicts in the top_k reconcile
    budget: with more conflicts than budget, the pinned row must still be
    reconciled."""
    import dataclasses as dc
    mcfg, cfg, emb, params, state = setup
    cfg = dc.replace(cfg, num_groups=2, max_reconcile_rows=4)
    emb = LazyEmbed(mcfg, cfg)
    pinned_row = 5
    # 16 genuinely conflicting rows (both groups) + the pinned row solo
    touched = jnp.stack([
        jnp.concatenate([jnp.full((4,), pinned_row, jnp.int32),
                         jnp.arange(100, 116, dtype=jnp.int32)]),
        jnp.concatenate([jnp.arange(300, 304, dtype=jnp.int32),
                         jnp.arange(100, 116, dtype=jnp.int32)]),
    ])
    state = init_state(cfg, mcfg.vocab)
    state = {**state,
             "streak": state["streak"].at[pinned_row].set(cfg.pin_streak)}
    grads = jnp.zeros((cfg.num_groups, mcfg.vocab, mcfg.d_model), jnp.float32)
    pos = emb.hash_touched(touched)
    sigs = emb.signatures(touched, pos=pos)
    pinned_mask = state["streak"][touched.reshape(-1)] >= cfg.pin_streak
    rows, valid = emb.detect_conflicts(touched, sigs, pos=pos,
                                       force=pinned_mask)
    assert rows.shape[0] == cfg.max_reconcile_rows  # budget is binding
    assert bool(jnp.any((rows == pinned_row) & valid))


def test_duplicate_pinned_entries_cannot_crowd_out_other_pins(setup):
    """A hot pinned row's duplicate touched entries must consume ONE budget
    slot, so a second pinned row is still reconciled, and a crowded-out row
    keeps (extends) its streak rather than silently unpinning."""
    import dataclasses as dc
    mcfg, cfg, emb, params, state = setup
    cfg = dc.replace(cfg, num_groups=2, max_reconcile_rows=4)
    emb = LazyEmbed(mcfg, cfg)
    params = emb.init(jax.random.key(0))
    a, b = 5, 6
    touched = jnp.stack([
        # group 0: A four times, B once, plus competing conflicts
        jnp.concatenate([jnp.full((4,), a, jnp.int32),
                         jnp.array([b], jnp.int32),
                         jnp.arange(100, 111, dtype=jnp.int32)]),
        jnp.concatenate([jnp.arange(300, 305, dtype=jnp.int32),
                         jnp.arange(100, 111, dtype=jnp.int32)]),
    ])
    state = init_state(cfg, mcfg.vocab)
    state = {**state, "streak": state["streak"].at[a].set(cfg.pin_streak)
                                               .at[b].set(cfg.pin_streak)}
    grads = jnp.zeros((cfg.num_groups, mcfg.vocab, mcfg.d_model), jnp.float32)
    params, state, m = emb.sync_step(params, state, touched, grads)
    assert int(m["lazy_pinned"]) == 2
    # both pinned rows keep their streak (still pinned next step)
    assert int(state["streak"][a]) >= cfg.pin_streak
    assert int(state["streak"][b]) >= cfg.pin_streak


def test_fused_kernel_conflict_path_matches(setup):
    """detect_conflicts via the fused Pallas kernel (packed sigs) must be
    bit-identical to the jnp path."""
    import dataclasses as dc
    mcfg, cfg, emb, params, state = setup
    emb_k = LazyEmbed(mcfg, dc.replace(cfg, use_kernel=True, interpret=True))
    touched, grads = _rand_touch_grads(mcfg, cfg, jax.random.key(9))
    sigs = emb.signatures(touched)
    rows, valid = emb.detect_conflicts(touched, sigs)
    rows_k, valid_k = emb_k.detect_conflicts(touched, sigs)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(rows_k))
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(valid_k))


def test_bytes_savings(setup):
    """Per-step coherence payload must be far below the dense all-reduce."""
    mcfg, cfg, emb, params, state = setup
    touched, grads = _rand_touch_grads(mcfg, cfg, jax.random.key(3))
    params, state, m = emb.sync_step(params, state, touched, grads)
    assert float(m["lazy_bytes"]) < 0.3 * float(m["dense_bytes"])
