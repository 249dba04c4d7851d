"""LazySync: the paper's speculative-signature coherence protocol applied to
sparse embedding-table synchronization in data-parallel training
(beyond-paper contribution; DESIGN.md §2.2).

Mapping from LazyPIM:

    PIM core            -> data-parallel replica group
    cache line          -> embedding row
    speculative writes  -> local (unsynced) row updates per group
    PIMWriteSet         -> per-group Bloom signature of touched row ids
    conflict detection  -> signature intersection across groups
    flush + merge       -> exact reconciliation of conflicting rows only
    partial commit      -> full table sync every K steps
    lock after 3 RBs    -> rows with persistent conflicts pinned to eager sync

The embedding table carries a leading group dim (G, V, d), sharded over the
``data`` axis, plus a committed ``base`` copy.  Updates are linear (SGD on
the embedding), so reconciliation is EXACT:

    new_row = base + sum_g (table_g[row] - base[row])

(no rollback needed — merges are commutative; this is strictly better than
the paper's re-execution and is recorded as a beyond-paper improvement).

Per step, instead of a dense (V, d) gradient all-reduce, groups exchange
2 Kbit signatures (64 words each) and reconcile at most
``max_reconcile_rows`` actually-conflicting rows.  Every ``commit_interval``
steps a full commit re-synchronizes everything and resets speculation.

Hot-path notes: on the default jnp path ``sync_step`` byte-slice-hashes
each touched row exactly *once* per step (the positions are shared between
signature build and conflict detection), against a :class:`SignatureSpec`
cached on the :class:`LazyEmbed` instance — the seed code re-built the spec
(and re-derived the H3 matrix) twice per step.  With
``LazySyncConfig.use_kernel=True`` conflict detection instead runs through
the fused Pallas kernel ``bloom_detect_conflicts_pallas`` on packed
signatures, which re-hashes the ids in-kernel (VMEM-local) rather than
reading precomputed positions.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.signatures import SignatureSpec, hash_positions, pack_bits
from repro.models import common as C


@dataclasses.dataclass(frozen=True)
class LazySyncConfig:
    num_groups: int = 4
    sig_bits: int = 2048
    num_segments: int = 4
    commit_interval: int = 16          # K: partial-commit period (steps)
    max_reconcile_rows: int = 1024     # per-step exact-reconcile budget
    pin_streak: int = 3                # paper's lock-after-3-rollbacks rule
    embed_lr: float = 0.05
    use_kernel: bool = False           # fused Pallas conflict-detect kernel
    interpret: bool = False            # run that kernel in Pallas interpret
    #                                    mode (tests off the chip)


def init_state(cfg: LazySyncConfig, vocab: int) -> dict:
    return {
        "step": jnp.zeros((), jnp.int32),
        "streak": jnp.zeros((vocab,), jnp.int8),   # consecutive-conflict count
    }


@dataclasses.dataclass(frozen=True)
class LazyEmbed:
    """Grouped speculative embedding: params {table: (G,V,d), base: (V,d)}."""

    model_cfg: C.ModelConfig
    cfg: LazySyncConfig

    def param_specs(self) -> dict:
        g = self.cfg.num_groups
        v, d = self.model_cfg.vocab, self.model_cfg.d_model
        dt = self.model_cfg.param_dtype
        return {
            "table": C.ParamSpec((g, v, d), ("batch", "vocab", "embed"), dt,
                                 "small_normal"),
            "base": C.ParamSpec((v, d), ("vocab", "embed"), dt, "small_normal"),
        }

    def init(self, rng) -> dict:
        v, d = self.model_cfg.vocab, self.model_cfg.d_model
        base = (jax.random.normal(rng, (v, d), jnp.float32) * 0.02).astype(
            self.model_cfg.param_dtype)
        table = jnp.broadcast_to(base, (self.cfg.num_groups,) + base.shape)
        return {"table": table, "base": base}

    # ---- forward ------------------------------------------------------------

    def lookup(self, params: dict, tokens: jax.Array) -> jax.Array:
        """tokens: (G, B/G, S) -> (G, B/G, S, d): each group reads its own
        speculative replica (= PIM core reading its own speculative cache)."""
        scale = jnp.asarray(self.model_cfg.d_model ** 0.5,
                            self.model_cfg.param_dtype)
        return jax.vmap(lambda t, ids: t[ids] * scale)(params["table"], tokens)

    def logits(self, params: dict, x: jax.Array) -> jax.Array:
        """x: (G, B/G, S, d) -> per-group tied-embedding logits."""
        return jax.vmap(lambda t, h: jnp.einsum("bsd,vd->bsv", h, t))(
            params["table"], x)

    # ---- speculative update + coherence --------------------------------------

    def apply_grads(self, params: dict, grads_table: jax.Array) -> dict:
        """Local speculative SGD on each group's replica (no cross-group
        communication — the speculation step)."""
        new = params["table"].astype(jnp.float32) - \
            self.cfg.embed_lr * grads_table.astype(jnp.float32)
        return {**params, "table": new.astype(params["table"].dtype)}

    @functools.cached_property
    def spec(self) -> SignatureSpec:
        """Signature geometry, built once per LazyEmbed (the cached H3
        byte-slice tables ride along; the seed code re-built this — and
        re-derived the hash matrix — on every signatures/detect call)."""
        return SignatureSpec(self.cfg.sig_bits, self.cfg.num_segments)

    def hash_touched(self, touched: jax.Array) -> jax.Array:
        """Byte-sliced H3 positions for all touched ids: (G*T, M) uint32.
        Computed once per step and shared by :meth:`signatures` and
        :meth:`detect_conflicts`."""
        return hash_positions(self.spec, touched.reshape(-1))

    def signatures(
        self, touched: jax.Array, pos: jax.Array | None = None
    ) -> jax.Array:
        """Per-group Bloom signatures of touched rows.

        touched: (G, T) int32 row ids -> (G, sig_bits) bool.  This is the
        entire per-step coherence payload: G x 256 B instead of V x d x 4 B.
        ``pos`` optionally supplies precomputed :meth:`hash_touched` output.
        """
        g, t = touched.shape
        if pos is None:
            pos = self.hash_touched(touched)
        pos_g = pos.reshape(g, t, -1).astype(jnp.int32)

        def one(p):
            staged = jnp.zeros((self.cfg.sig_bits + 1,), bool)
            return staged.at[p.reshape(-1)].set(True, mode="drop")[:-1]

        return jax.vmap(one)(pos_g)

    def detect_conflicts(
        self,
        touched: jax.Array,
        sigs: jax.Array,
        pos: jax.Array | None = None,
        force: jax.Array | None = None,
        with_mask: bool = False,
    ):
        """Row ids touched by >= 2 groups (with the signatures' real FPs).

        ``force`` (G*T,) bool marks touched entries that must be reconciled
        regardless of signature hits — the §5.5 pin rule routes persistent
        conflicters through here.  Returns (row_ids (R,), valid (R,)) with
        R = max_reconcile_rows; with ``with_mask=True`` additionally returns
        the full per-entry conflict mask (G*T,) *before* budget truncation
        (used by ``sync_step`` for streak accounting).
        """
        g, t = touched.shape
        flat = touched.reshape(-1)
        if self.cfg.use_kernel:
            # fused kernel hashes in-kernel; ``pos`` is not needed here
            from repro.kernels.bloom import bloom_detect_conflicts

            packed = jax.vmap(lambda b: pack_bits(self.spec, b))(sigs)
            hit_groups = bloom_detect_conflicts(
                self.spec, packed, flat, use_pallas=True,
                interpret=self.cfg.interpret,
            )
        else:
            if pos is None:
                pos = self.hash_touched(touched)
            pos = pos.astype(jnp.int32)  # (G*T, M)
            # membership of every touched id in every group's signature
            member = jnp.all(sigs[:, pos], axis=-1)          # (G, G*T)
            hit_groups = jnp.sum(member, axis=0)             # (G*T,)
        conflict = hit_groups >= 2
        if force is not None:
            conflict = conflict | force.reshape(-1)
        # Budget selection: score only the FIRST occurrence of each row, so
        # one hot row's duplicate entries consume one top_k slot, not k.
        # Forced (pinned) rows outrank ordinary conflicts so the
        # must-reconcile guarantee survives budget pressure (ties inside
        # top_k are arbitrary).
        n = flat.shape[0]
        vocab = self.model_cfg.vocab
        order = jnp.arange(n, dtype=jnp.int32)
        first = jnp.full((vocab,), n, jnp.int32).at[flat].min(order, mode="drop")
        is_first = first[flat] == order
        score = jnp.where(is_first & conflict, 1.0, 0.0)
        if force is not None:
            score = jnp.where(is_first & force.reshape(-1), 2.0, score)
        _, idx = jax.lax.top_k(score, min(self.cfg.max_reconcile_rows, n))
        rows = flat[idx]
        valid = score[idx] > 0  # unique conflicting/forced rows only
        if with_mask:
            return rows, valid, conflict
        return rows, valid

    def reconcile(self, params: dict, rows: jax.Array, valid: jax.Array) -> dict:
        """Exact merge of conflicting rows (the WAW dirty-bit-mask merge):
        new = base + sum_g (table_g - base); all replicas + base updated."""
        table, base = params["table"], params["base"]
        safe = jnp.where(valid, rows, 0)
        t_rows = table[:, safe, :].astype(jnp.float32)       # (G, R, d)
        b_rows = base[safe, :].astype(jnp.float32)           # (R, d)
        merged = b_rows + jnp.sum(t_rows - b_rows[None], axis=0)
        merged = jnp.where(valid[:, None], merged, b_rows)
        new_base = base.at[safe].set(
            jnp.where(valid[:, None], merged, b_rows).astype(base.dtype))
        new_table = table.at[:, safe, :].set(
            jnp.where(valid[None, :, None], merged[None], t_rows).astype(table.dtype))
        return {"table": new_table, "base": new_base}

    def commit(self, params: dict) -> dict:
        """Partial commit (every K steps): full exact sync of all rows."""
        table, base = params["table"].astype(jnp.float32), params["base"].astype(jnp.float32)
        new = base + jnp.sum(table - base[None], axis=0)
        new = new.astype(params["base"].dtype)
        g = self.cfg.num_groups
        return {"table": jnp.broadcast_to(new, (g,) + new.shape), "base": new}

    # ---- one protocol step -----------------------------------------------------

    def sync_step(self, params: dict, state: dict, touched: jax.Array,
                  grads_table: jax.Array):
        """Speculative apply -> signature exchange -> conflict reconcile ->
        periodic commit.  Returns (params, state, metrics)."""
        cfg = self.cfg
        params = self.apply_grads(params, grads_table)
        # hash every touched row exactly once; signatures() and
        # detect_conflicts() share the positions
        pos = self.hash_touched(touched)
        sigs = self.signatures(touched, pos=pos)

        # pin rule (paper §5.5 lock-after-3): rows whose conflict streak has
        # reached pin_streak are forced into the reconcile set (eager sync)
        # even when no signature conflict fires this step.
        streak = state["streak"]
        flat = touched.reshape(-1)
        pinned_mask = streak[flat] >= cfg.pin_streak  # (G*T,)
        rows, valid, conflict_mask = self.detect_conflicts(
            touched, sigs, pos=pos, force=pinned_mask, with_mask=True
        )

        # streak accounting, from the FULL pre-budget conflict mask: each
        # *unique* conflicting row extends its streak by exactly 1 (a
        # scatter-add over entries would count duplicate touches k times —
        # and wrap int8 at 256 — for hot rows), including rows the top_k
        # budget could not fit this step (they keep ratcheting toward the
        # pin, whose 2.0 priority then guarantees reconciliation).  Rows
        # touched WITHOUT conflicting reset to 0 — the streak is a
        # *consecutive*-conflict count (§5.5 "3 rollbacks in a row"), not a
        # cumulative one; untouched rows keep their streak.
        vocab = streak.shape[0]
        mark = jnp.zeros((vocab + 1,), bool).at[
            jnp.where(conflict_mask, flat, vocab)
        ].set(True, mode="drop")[:vocab]
        touched_mark = jnp.zeros((vocab + 1,), bool).at[flat].set(
            True, mode="drop"
        )[:vocab]
        bumped = jnp.minimum(streak.astype(jnp.int32) + 1, 127).astype(jnp.int8)
        streak = jnp.where(
            mark, bumped, jnp.where(touched_mark, jnp.int8(0), streak)
        )

        params = self.reconcile(params, rows, valid)

        step = state["step"] + 1
        do_commit = (step % cfg.commit_interval) == 0
        params = jax.lax.cond(do_commit, self.commit, lambda p: p, params)
        streak = jnp.where(do_commit, jnp.zeros_like(streak), streak)

        # unique pinned *rows* (summing pinned_mask would count a hot row
        # once per duplicate touched entry)
        pin_mark = jnp.zeros((vocab + 1,), bool).at[
            jnp.where(pinned_mask, flat, vocab)
        ].set(True, mode="drop")[:vocab]

        n_conflicts = jnp.sum(valid)
        metrics = {
            "lazy_conflict_rows": n_conflicts,
            "lazy_pinned": jnp.sum(pin_mark),
            "lazy_commit": do_commit,
            # comm accounting (bytes): signatures + reconciled rows vs dense
            "lazy_bytes": (cfg.num_groups * cfg.sig_bits // 8
                           + n_conflicts * self.model_cfg.d_model * 4
                           + jnp.where(do_commit,
                                       self.model_cfg.vocab * self.model_cfg.d_model * 4,
                                       0)),
            "dense_bytes": self.model_cfg.vocab * self.model_cfg.d_model * 4,
        }
        return params, {"step": step, "streak": streak}, metrics
