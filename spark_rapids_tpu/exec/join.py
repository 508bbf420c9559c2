"""TPU hash join.

Reference behavior: rapids/GpuHashJoin.scala:26-139 — build side becomes a
table, each stream batch projects its keys and runs
innerJoin/leftJoin/leftSemiJoin/leftAntiJoin, with residual conditions
applied as a post-filter (inner only); GpuShuffledHashJoinExec.scala:83-87
requires a single build batch.

TPU-first implementation: no hash table (scatter-heavy probing is slow on
TPU).  The join is sort + merge with static shapes, shaped like cuDF's own
count-then-gather join API:

  1. BUILD: hash the build keys (64-bit), stable-sort the build batch by
     hash — dead rows hash to uint64-max and fall to the back.  Done once,
     then reused for every stream batch.
  2. WINDOW: the stream batch's hashes MERGE into the sorted build hashes
     (`utils/packed_sort.merge_windows`: one single-operand sort of both
     sides' packed hash prefixes, two prefix scans, two sorts back to
     stream order), which yields per stream row the candidate window
     [lo, hi) of build rows sharing its hash prefix.  No binary search and
     no gather: on the v5e a 1M-row gather of a uint64 costs 17 ms, a
     search chains 21 of them a side, and a 2M-word sort costs 3.8 ms
     (PR 30, `packed_sort.py`'s header).  One host sync reads the max
     window width, which becomes the static `max_dup` of the probe
     kernels (hash and prefix collisions inside a window are rejected by
     comparing the actual key bytes, so a wide window is a cost, never a
     wrongness).
  3. COUNT: `fori_loop` over d < max_dup verifies candidate d of every
     stream row's window (key bytes, residual condition), counts the
     matches per row and KEEPS which candidates matched: bit d of a
     per-row `uint32` word (`ceil(max_dup / 32)` words a row).  Prefix
     sums give each row's output start and the total (the host reads the
     total with the window width and picks the power-of-two output
     capacity bucket).
  4. GATHER, in OUTPUT space: nothing is verified twice and no loop runs.
     Every stream row with output writes its row number at its first
     output slot (one sort that compacts those rows, one scatter a batch)
     and a running maximum fills its run: each slot knows its stream row.  The slot's rank inside the run
     picks the rank-th set bit of the row's word (a closed form over
     popcounts), which is the build row's offset in the window.  The cost
     follows the output's capacity, not the stream's (PR 36: the loop
     this replaced gathered and scattered over the whole stream batch
     every step, 61 ms a 1M-row batch to write 17,000 rows).
     semi/anti never reach this phase (they are a mask over the stream
     batch: counts>0 / counts==0).
  5. PASS-THROUGH, where the gather would only copy the stream batch: no
     live stream row has more than one candidate (`max_dup <= 1`, so no
     row multiplies) and the output's capacity bucket is the stream
     batch's own.  The output is then the stream batch itself, its column
     arrays untouched, each row beside its one build row or none, the
     selection ANDed with "matched" for an inner join.  Dead slots stay
     in place; the live rows keep the order the gather would give them.
     Chosen from the two integers the probe's one host read already
     returned: a join that multiplies or compacts rows keeps phase 4.

Equality uses Spark key semantics (nulls never match, NaN == NaN,
-0.0 == 0.0), matching the CPU oracle in cpu_relational.py.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, ColumnarBatch, concat_batches
from ..columnar.batch import bucket_rows
from ..utils import pow2_bucket as _pow2_bucket
from ..utils.tracing import named_range
from ..ops import expressions as E
from ..ops.hashing import _normalize_bits, hash_columns_double
from ..types import Schema, StructField
from .base import ExecContext, ExecNode, TpuExec
from ..metrics import names as MN


def _pvary(x, axes):
    """Mark a freshly-created array as varying over shard_map manual axes so
    fori_loop carries typecheck (no-op when not under shard_map)."""
    if not axes:
        return x
    return jax.lax.pcast(x, axes, to="varying")


def _row_equal(lcol: Column, bcol: Column, bidx):
    """Per-stream-row key equality between lcol[i] and bcol[bidx[i]]
    (Spark join-key semantics: null keys never match anything)."""
    bvalid = jnp.take(bcol.valid, bidx, mode="clip")
    ok = lcol.valid & bvalid
    if lcol.dtype.is_string:
        blens = jnp.take(bcol.lengths, bidx, mode="clip")
        ok &= lcol.lengths == blens
        bdata = jnp.take(bcol.data, bidx, axis=0, mode="clip")
        L = min(lcol.max_len, bcol.max_len)
        pos = jnp.arange(L, dtype=jnp.int32)[None, :]
        in_str = pos < lcol.lengths[:, None]
        same = jnp.where(in_str, lcol.data[:, :L] == bdata[:, :L], True)
        ok &= jnp.all(same, axis=1)
    else:
        lbits = _normalize_bits(lcol)
        bbits = jnp.take(_normalize_bits(bcol), bidx, mode="clip")
        ok &= lbits == bbits
    return ok


def _nth_set_bit(word, n):
    """Position of set bit number `n` (0-based, from the LSB) of each
    uint32 `word`; meaningless where the word has at most `n` bits set.
    Five halvings on popcounts: elementwise, no loop."""
    pos = jnp.zeros(word.shape, jnp.int32)
    for width in (16, 8, 4, 2, 1):
        low = (word >> pos.astype(jnp.uint32)) & jnp.uint32((1 << width) - 1)
        below = jax.lax.population_count(low).astype(jnp.int32)
        up = n >= below
        pos = jnp.where(up, pos + width, pos)
        n = jnp.where(up, n - below, n)
    return pos


def _build_columns_at(build: ColumnarBatch, b_idx, matched):
    """The build side's columns taken at `b_idx`, null (and zeroed) where
    the slot has no matched build row."""
    rcols = []
    for c in build.columns:
        taken = c.take(b_idx)
        rcols.append(taken.with_valid(taken.valid & matched).mask_invalid())
    return rcols


def _build_hits(cap_b: int, b_idx, matched, vary_axes: tuple = ()):
    """Which build rows a matched slot points at (a full join's tail is
    the build rows no stream row ever matched)."""
    b_hit = _pvary(jnp.zeros(cap_b, jnp.bool_), vary_axes)
    return b_hit.at[jnp.where(matched, b_idx, cap_b)].set(True, mode="drop")


class TpuReorderColumnsExec(TpuExec):
    """Column selection pass-through: side-swapped joins (right outer as
    a swapped left join; build-side-selected inner joins) emit
    [R..., L...], and this selects/reorders the output columns back to
    the logical plan's order — for USING joins it also drops the
    duplicated key columns (names come from the final schema)."""

    def __init__(self, child: ExecNode, perm: Sequence[int],
                 out_schema: Schema):
        super().__init__(child)
        self.perm = list(perm)
        self._schema = out_schema

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"TpuReorderColumnsExec[{len(self.perm)} cols]"

    def execute(self, ctx):
        for b in self.children[0].execute(ctx):
            sb = b.select_columns(self.perm)
            yield ColumnarBatch(sb.columns, sb.sel, self._schema)


class TpuHashJoinExec(TpuExec):
    """Equi hash join: inner / left / full / left_semi / left_anti
    (right outer joins arrive side-swapped under TpuReorderColumnsExec).

    Streams the LEFT side against a single sorted build batch of the RIGHT
    side (reference builds right for these join types too,
    GpuHashJoin.scala:46-70)."""

    coalesce_after = True

    def __init__(self, left: ExecNode, right: ExecNode, join_type: str,
                 left_keys: Sequence[E.Expression],
                 right_keys: Sequence[E.Expression],
                 condition: Optional[E.Expression], out_schema: Schema,
                 using_drop: Optional[List[int]] = None):
        super().__init__(left, right)
        # canonical names so kernels only ever see "left"/"full"
        self.join_type = {"left_outer": "left",
                          "full_outer": "full"}.get(join_type, join_type)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.condition = condition
        self._schema = out_schema
        self.using_drop = using_drop or []

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return (f"TpuHashJoinExec[{self.join_type}, "
                f"keys={len(self.left_keys)}]")

    def kernel_key(self) -> tuple:
        from ..utils.kernel_cache import expr_key, schema_key
        # schemas matter: the gather kernel closes over self._schema, and
        # two joins with identical key exprs can differ in payload columns
        return ("TpuHashJoinExec", self.join_type,
                tuple(expr_key(e) for e in self.left_keys),
                tuple(expr_key(e) for e in self.right_keys),
                expr_key(self.condition) if self.condition is not None
                else None,
                tuple(self.using_drop),
                schema_key(self.children[0].schema),
                schema_key(self.children[1].schema),
                schema_key(self._schema))

    # ---- kernels ----------------------------------------------------------

    def _build_kernel(self, rbatch: ColumnarBatch):
        """Sort the build batch by key hash; dead rows last."""
        keys = [e.eval(rbatch) for e in self.right_keys]
        h1, _h2 = hash_columns_double(keys, rbatch.sel)
        from ..utils.packed_sort import stable_argsort
        order = stable_argsort([(h1, 64)], rbatch.capacity)
        sorted_batch = rbatch.take(order)
        skeys = [k.take(order) for k in keys]
        return sorted_batch, skeys, jnp.take(h1, order)

    def _window_kernel(self, lbatch: ColumnarBatch, h1s):
        """-> (lo, hi, max_dup) candidate windows per stream row."""
        keys = [e.eval(lbatch) for e in self.left_keys]
        h1, _h2 = hash_columns_double(keys, lbatch.sel)
        from ..utils.packed_sort import merge_windows
        return merge_windows(h1s, h1, lbatch.sel)

    @staticmethod
    def _joined_fields(lschema: Schema, rschema: Schema):
        """Joined-output fields: left fields as-is, right fields renamed
        `name_r` on collision.  The ONE definition shared by the pair-
        condition view, the gather output, and the full-outer tail — the
        three must agree or the condition sees a different schema than
        the output rows."""
        lfields = list(lschema.fields)
        rfields = [StructField(f.name + "_r"
                               if f.name in lschema.names else f.name,
                               f.dtype) for f in rschema]
        return lfields, rfields

    def _joined_batch(self, lcols, rcols, sel, lschema: Schema,
                      rschema: Schema) -> ColumnarBatch:
        """The output batch from its left and right column blocks: the
        joined fields, USING's duplicated key columns dropped, the join's
        own schema."""
        lfields, rfields = self._joined_fields(lschema, rschema)
        joined = ColumnarBatch(list(lcols) + list(rcols), sel,
                               Schema(lfields + rfields))
        if self.using_drop:
            keep_idx = [i for i in range(joined.num_cols)
                        if i not in self.using_drop]
            joined = joined.select_columns(keep_idx)
        return ColumnarBatch(joined.columns, joined.sel, self._schema)

    def _pair_condition_ok(self, lbatch: ColumnarBatch,
                           build: ColumnarBatch, bidx):
        """Residual-condition mask for candidate pairs (left row i, build
        row bidx[i]): gathers build columns at bidx into a joined-schema
        view and evaluates the condition vectorized.  Beyond the
        reference's inner-only conditional joins (GpuHashJoin tagJoin):
        evaluating inside the candidate walk gives conditional
        left_semi/left_anti exact per-pair semantics."""
        lcols = list(lbatch.columns)
        rcols = [c.take(bidx) for c in build.columns]
        lfields, rfields = self._joined_fields(lbatch.schema, build.schema)
        pair = ColumnarBatch(lcols + rcols, lbatch.sel,
                             Schema(lfields + rfields))
        cond = self.condition.eval(pair)
        return cond.valid & cond.data.astype(jnp.bool_)

    def _probe_kernel(self, max_dup_guess: int, lbatch: ColumnarBatch,
                      build: ColumnarBatch, bkeys, h1s):
        """Fused window+count with a SPECULATIVE duplication bucket: one
        dispatch computes the candidate windows AND the verified counts
        for `max_dup_guess`; the counts are valid iff the true max
        duplication fits the guess (the caller checks in the same scalar
        fetch that reads the total — ONE host sync per probe batch
        instead of the window/count pair's two: one round trip over the
        host link instead of two).  XLA CSEs the key evaluation
        shared by the window and count phases."""
        lo, hi, md = self._window_kernel(lbatch, h1s)
        counts, starts, total, hits = self._count_kernel(
            max_dup_guess, lbatch, build, bkeys, lo, hi)
        return lo, hi, counts, starts, hits, \
            jnp.stack([md.astype(jnp.int64), total.astype(jnp.int64)])

    def _count_kernel(self, max_dup: int, lbatch: ColumnarBatch,
                      build: ColumnarBatch, bkeys, lo, hi,
                      vary_axes: tuple = ()):
        """Verified match count per stream row + prefix starts + total +
        `hits`: which candidates of each row's window matched, bit d % 32
        of word d // 32 for candidate `lo + d` (a tuple of
        `ceil(max_dup / 32)` uint32 arrays).  The residual condition
        (when present) participates in the count and the bits, so
        semi/anti membership and the inner pair count are exact and the
        gather verifies nothing again."""
        lkeys = [e.eval(lbatch) for e in self.left_keys]
        cap_b = build.capacity
        live = lbatch.sel
        blive = build.sel

        def body(d, carry):
            cnt, word = carry
            bidx = jnp.clip(lo + d, 0, cap_b - 1)
            ok = live & ((lo + d) < hi) & jnp.take(blive, bidx, mode="clip")
            for lk, bk in zip(lkeys, bkeys):
                ok &= _row_equal(lk, bk, bidx)
            if self.condition is not None:
                ok &= self._pair_condition_ok(lbatch, build, bidx)
            bit = ok.astype(jnp.uint32) << (d % 32).astype(jnp.uint32)
            return cnt + ok.astype(jnp.int32), word | bit

        counts = _pvary(jnp.zeros(lbatch.capacity, jnp.int32), vary_axes)
        hits = []
        for first in range(0, max(max_dup, 1), 32):
            counts, word = jax.lax.fori_loop(
                first, min(first + 32, max_dup), body,
                (counts, _pvary(jnp.zeros(lbatch.capacity, jnp.uint32),
                                vary_axes)))
            hits.append(word)
        if self.join_type in ("left", "full"):
            # an unmatched live row takes one output slot; its word stays 0
            counts = jnp.where(live & (counts == 0), 1, counts)
        starts = jnp.cumsum(counts) - counts
        return counts, starts, jnp.sum(counts), tuple(hits)

    def _gather_kernel(self, out_cap: int, lbatch: ColumnarBatch,
                       build: ColumnarBatch, lo, counts, starts, total,
                       hits, vary_axes: tuple = ()):
        """Place the (left_row, build_row) pairs from the OUTPUT's side,
        then gather the joined columns.  Row i owns the slots
        `[starts[i], starts[i] + counts[i])`; slot `starts[i] + rank`
        holds its rank-th verified candidate in window order (the order
        the walk this replaced wrote them in).  No key is compared and
        nothing loops: one single-operand sort at stream capacity, one
        scatter over min(stream, output) rows, two scans and two gathers
        at output capacity (one more gather a further word of `hits`),
        the rest elementwise."""
        slots = jnp.arange(out_cap, dtype=jnp.int32)
        rows = jnp.arange(lbatch.capacity, dtype=jnp.int32)
        sel = slots < total

        # l_idx: the first slots of the rows with output are distinct and
        # rise with the row, so a running maximum over the marks fills
        # each row's run.  One single-operand sort of `first slot | row`
        # brings those rows to the front, so the scatter that marks the
        # slots runs over min(stream, output) rows: on the v5e a 1M-row
        # scatter costs 5.6 ms and this sort 1.7 (PR 36's probe).  (A slot
        # past out_cap: the mesh's guessed capacity overflowed, the
        # driver retries.)
        r = max(1, (lbatch.capacity - 1).bit_length())
        none = jnp.uint64(2**64 - 1)
        word = (starts.astype(jnp.uint64) << jnp.uint64(r)) \
            | rows.astype(jnp.uint64)
        front = jax.lax.sort(jnp.where(counts > 0, word, none), dimension=0,
                             is_stable=False)[:min(lbatch.capacity, out_cap)]
        first = jnp.where(front == none, out_cap,
                          (front >> jnp.uint64(r)).astype(jnp.int32))
        row = (front & jnp.uint64((1 << r) - 1)).astype(jnp.int32)
        marks = _pvary(jnp.zeros(out_cap, jnp.int32), vary_axes)
        l_idx = jax.lax.cummax(marks.at[first].set(row, mode="drop"))
        run_start = jnp.concatenate(
            [jnp.ones(1, jnp.bool_), l_idx[1:] != l_idx[:-1]])
        rank = slots - jax.lax.cummax(jnp.where(run_start, slots, 0))

        # b_idx: the window's start + the position of set bit number
        # `rank` of the row's words; a left/full row without a match has
        # rank 0 and no bit: `matched` False, the right side null
        offset = jnp.zeros(out_cap, jnp.int32)
        matched = jnp.zeros(out_cap, jnp.bool_)
        for w, word in enumerate(hits):
            word = jnp.take(word, l_idx, mode="clip")
            n = jax.lax.population_count(word).astype(jnp.int32)
            here = ~matched & (rank < n)
            offset = jnp.where(here, 32 * w + _nth_set_bit(word, rank),
                               offset)
            matched |= here
            rank = rank - n
        matched &= sel
        l_idx = jnp.where(sel, l_idx, 0)
        b_idx = jnp.where(
            matched, jnp.take(lo, l_idx, mode="clip") + offset, 0)

        lcols = [c.take(l_idx) for c in lbatch.columns]
        rcols = _build_columns_at(build, b_idx, matched)
        # no post-filter: the residual condition (if any) was already
        # applied pair-wise in the count walk, so slots and counts
        # agree by construction
        out = self._joined_batch(lcols, rcols, sel, lbatch.schema,
                                 build.schema)
        if self.join_type == "full":
            # which BUILD rows ever matched, so the stream driver can
            # emit the never-matched remainder
            return out, _build_hits(build.capacity, b_idx, matched,
                                    vary_axes)
        return out

    def _passthrough_kernel(self, lbatch: ColumnarBatch,
                            build: ColumnarBatch, lo, hits):
        """The right side of a pass-through output (module docstring,
        phase 5): every live stream row has at most one candidate, `lo`,
        and bit 0 of its first `hits` word says whether the count walk
        verified it (keys, build row live, residual condition).  Returns
        the output's selection, the build side's columns at stream
        capacity and, for `full`, which build rows matched.  The stream
        batch's columns are not outputs: an array a program returns is a
        copy, so the caller puts the stream batch's own arrays in front."""
        matched = lbatch.sel & ((hits[0] & jnp.uint32(1)) != 0)
        b_idx = jnp.where(matched, lo, 0)
        rcols = _build_columns_at(build, b_idx, matched)
        # left / full: an unmatched live row stays, its right side null
        sel = matched if self.join_type == "inner" else lbatch.sel
        b_hit = _build_hits(build.capacity, b_idx, matched) \
            if self.join_type == "full" else None
        return sel, rcols, b_hit

    def _full_remainder(self, build: ColumnarBatch, b_hit) -> ColumnarBatch:
        """FULL OUTER tail: build rows no stream row ever matched, with
        the left side all-null (emitted once, after the whole stream)."""
        lschema = self.children[0].schema
        lcols = [Column.all_null(f.dtype, build.capacity)
                 for f in lschema]
        return self._joined_batch(lcols, build.columns, build.sel & ~b_hit,
                                  lschema, build.schema)

    def _semi_kernel(self, lbatch: ColumnarBatch, counts):
        if self.join_type == "left_semi":
            return lbatch.filter(counts > 0)
        return lbatch.filter(counts == 0)  # left_anti

    # ---- driver -----------------------------------------------------------

    def _cpu_twin(self):
        """CPU re-execution plan for OOM fallback (exec/retryable.py):
        the CPU join over both device children bridged through D2H
        (CpuJoinExec accepts the canonical left/full type names)."""
        from .basic import DeviceToHostExec
        from .cpu_relational import CpuJoinExec
        return CpuJoinExec(DeviceToHostExec(self.children[0]),
                           DeviceToHostExec(self.children[1]),
                           self.join_type, self.left_keys, self.right_keys,
                           self.condition, self._schema, self.using_drop)

    def execute(self, ctx: ExecContext):
        from .retryable import execute_with_cpu_fallback
        yield from execute_with_cpu_fallback(
            self, ctx, self._execute_device(ctx), self._cpu_twin)

    def _execute_device(self, ctx: ExecContext):
        rbatches = list(self.children[1].execute(ctx))
        if rbatches:
            rbatch = rbatches[0] if len(rbatches) == 1 \
                else concat_batches(rbatches)
            # filtered build sides ride their input capacity otherwise —
            # the build sort and every probe window pay for dead rows
            rbatch = rbatch.maybe_shrink(self._live_rows_host(rbatch))
        else:
            rbatch = _empty_batch(self.children[1].schema)
        yield from self._join_stream(rbatch, self.children[0].execute(ctx),
                                     ctx)

    def _live_rows_host(self, batch: ColumnarBatch) -> int:
        """`batch.num_rows_host()`, counted in joinHostSyncs where it has
        to read the device for it."""
        if batch.known_rows is None:
            self.metrics.add(MN.JOIN_HOST_SYNCS, 1)
        return batch.num_rows_host()

    def _join_stream(self, rbatch: ColumnarBatch, lbatches, ctx=None):
        """Build once from `rbatch`, stream left batches through the probe
        kernels.  Shared by the whole-build path (execute) and the
        per-partition path (TpuShuffledHashJoinExec)."""
        from ..utils.kernel_cache import cached_kernel
        from .retryable import run_retryable, split_batch_rows
        key = self.kernel_key()
        build_fn = cached_kernel(key + ("build",),
                                 lambda: self._build_kernel)
        # present and 0 where the gather answers every stream batch
        self.metrics.add(MN.JOIN_PASS_THROUGH_BATCHES, 0)

        def attempt_build(rb):
            # retry-only: the single-build-batch contract forbids
            # splitting the build side (exhaustion -> CPU fallback)
            if ctx is not None and ctx.runtime is not None:
                ctx.runtime.reserve(rb.device_size_bytes(),
                                    site="join.build")
            return build_fn(rb)

        with named_range("join_build", self.metrics, MN.BUILD_TIME):
            if ctx is not None:
                build, bkeys, h1s = run_retryable(
                    ctx, self.metrics, "joinBuild", attempt_build,
                    [rbatch])[0]
            else:
                build, bkeys, h1s = build_fn(rbatch)

        def probe_one(lb):
            """One stream batch through the probe kernels.  Retryable and
            row-splittable: every supported join type is per-left-row
            independent given the resident build side, so the outputs of
            split pieces compose by concatenation (full-outer build-hit
            masks OR together in the driver)."""
            if ctx is not None and ctx.runtime is not None:
                ctx.runtime.reserve(lb.device_size_bytes(),
                                    site="join.probe")
            # SPECULATIVE probe: window+count fuse into one dispatch
            # using the previous batch's duplication bucket (stream
            # skew is stable batch to batch); the single scalar fetch
            # below reads the true max_dup AND the total together.
            # Power-of-two buckets: raw data-dependent integers in
            # the kernel-cache key would recompile per distinct skew.
            guess = getattr(self, "_dup_guess", 8)
            probe_fn = cached_kernel(
                key + ("probe", guess),
                lambda: functools.partial(self._probe_kernel, guess))
            lo, hi, counts, starts, hits, scalars_t = probe_fn(
                lb, build, bkeys, h1s)
            self.metrics.add(MN.JOIN_MERGED_WINDOW_BATCHES, 1)
            self.metrics.add(MN.JOIN_WALK_STEPS, guess)
            self.metrics.add(MN.JOIN_HOST_SYNCS, 1)
            md, total = (int(x) for x in np.asarray(scalars_t))
            max_dup = _pow2_bucket(md)
            self._dup_guess = max_dup
            if max_dup > guess:
                # speculation failed (skew grew): recount with the
                # right bucket — one extra dispatch+sync, this batch
                count_fn = cached_kernel(
                    key + ("count", max_dup),
                    lambda: functools.partial(self._count_kernel,
                                              max_dup))
                counts, starts, total_t, hits = count_fn(
                    lb, build, bkeys, lo, hi)
                self.metrics.add(MN.JOIN_WALK_STEPS, max_dup)
                self.metrics.add(MN.JOIN_HOST_SYNCS, 1)
                total = int(total_t)
            if self.join_type in ("left_semi", "left_anti"):
                semi_fn = cached_kernel(key + ("semi",),
                                        lambda: self._semi_kernel)
                out = semi_fn(lb, counts)
                self.metrics.add(MN.JOIN_SEMI_BATCHES, 1)
                out = ColumnarBatch(out.columns, out.sel, self._schema)
                return out, None, total
            out_cap = bucket_rows(max(total, 1))
            # the words the window width just read can reach: a walk at
            # a wider guess set no bit past it
            hits = hits[:max(1, -(-max_dup // 32))]
            b_hit = None
            if md <= 1 and out_cap == lb.capacity:
                # inner, left or full (semi and anti returned above): no
                # row multiplies and the gather would write a batch of
                # the stream's own capacity: pass the stream batch through
                pass_fn = cached_kernel(key + ("passthrough", len(hits)),
                                        lambda: self._passthrough_kernel)
                sel, rcols, b_hit = pass_fn(lb, build, lo, hits)
                out = self._joined_batch(lb.columns, rcols, sel, lb.schema,
                                         build.schema)
                self.metrics.add(MN.JOIN_PASS_THROUGH_BATCHES, 1)
            else:
                gather_fn = cached_kernel(
                    key + ("gather", len(hits), out_cap),
                    lambda: functools.partial(self._gather_kernel, out_cap))
                out = gather_fn(lb, build, lo, counts, starts,
                                jnp.int64(total), hits)
                self.metrics.add(MN.JOIN_OUTPUT_SPACE_BATCHES, 1)
                if self.join_type == "full":
                    out, b_hit = out
            # the fetched total IS the live-row count: hand it to
            # downstream adaptive shrinks so they skip their sync
            out.known_rows = total
            return out, b_hit, total

        b_hit_accum = None  # full join: OR of per-batch build-hit masks
        for lbatch in lbatches:
            with named_range("join_stream", self.metrics, MN.JOIN_TIME):
                if ctx is not None:
                    results = run_retryable(ctx, self.metrics, "joinProbe",
                                            probe_one, [lbatch],
                                            split=split_batch_rows)
                else:
                    results = [probe_one(lbatch)]
            for out, b_hit, _total in results:
                if b_hit is not None:
                    b_hit_accum = b_hit if b_hit_accum is None \
                        else b_hit_accum | b_hit
                self.metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
                # deferred: an int() here is a device sync PER OUTPUT
                # BATCH (a host-link round trip) in the join hot loop
                self.metrics.add_lazy(MN.NUM_OUTPUT_ROWS, out.num_rows())
                yield out
        if self.join_type == "full":
            if b_hit_accum is None:
                b_hit_accum = jnp.zeros(build.capacity, jnp.bool_)
            with named_range("join_full_tail", self.metrics, MN.JOIN_TIME):
                tail = self._full_remainder(build, b_hit_accum)
            n = self._live_rows_host(tail)
            if n:
                self.metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
                self.metrics.add(MN.NUM_OUTPUT_ROWS, n)
                yield tail


def _empty_batch(schema: Schema) -> ColumnarBatch:
    data = {f.name: [] for f in schema}
    return ColumnarBatch.from_pydict(data, schema)


class TpuShuffledHashJoinExec(TpuHashJoinExec):
    """Partitioned hash join: both children are hash exchanges on the join
    keys with the SAME partition count, so the single-build-batch bound
    holds PER PARTITION instead of per input (reference:
    rapids/GpuShuffledHashJoinExec.scala:83-87 — Spark's EnsureRequirements
    places matching HashPartitionings; here the planner inserts the
    exchanges directly, plan/physical.py)."""

    def describe(self):
        n = self.children[1].num_partitions
        return (f"TpuShuffledHashJoinExec[{self.join_type}, "
                f"keys={len(self.left_keys)}, partitions={n}]")

    def _execute_device(self, ctx: ExecContext):
        from .exchange import TpuShuffleExchangeExec
        from .shuffle_reader import TpuCoalescedShuffleReaderExec
        lex, rex = self.children
        # children are either the planner's aligned hash exchanges, or —
        # after adaptive re-planning — paired shuffle readers holding
        # spec lists of identical length (coalesced ranges merged the
        # same way on both sides; skew slices paired with replicated
        # build partitions)
        assert isinstance(lex, (TpuShuffleExchangeExec,
                                TpuCoalescedShuffleReaderExec)) \
            and isinstance(rex, (TpuShuffleExchangeExec,
                                 TpuCoalescedShuffleReaderExec)) \
            and lex.num_partitions == rex.num_partitions, \
            "shuffled join requires aligned hash exchanges on both sides"
        produced = False
        for (lp, lbatch), (rp, rbatch) in zip(
                lex.execute_partitions(ctx), rex.execute_partitions(ctx)):
            assert lp == rp
            if lbatch is None:
                if self.join_type != "full" or rbatch is None:
                    # no left rows in this partition: inner/left/semi/anti
                    # produce nothing from it — but FULL OUTER must still
                    # emit this partition's build rows with left nulls
                    continue
                tail = self._full_remainder(
                    rbatch, jnp.zeros(rbatch.capacity, jnp.bool_))
                n = self._live_rows_host(tail)
                if n:
                    produced = True
                    self.metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
                    self.metrics.add(MN.NUM_OUTPUT_ROWS, n)
                    yield tail
                continue
            if rbatch is None:
                rbatch = _empty_batch(rex.schema)
            produced = True
            yield from self._join_stream(rbatch, [lbatch], ctx)
        if not produced:
            # downstream operators (e.g. a global aggregate) require at
            # least one batch to carry empty-input semantics
            yield _empty_batch(self._schema)
