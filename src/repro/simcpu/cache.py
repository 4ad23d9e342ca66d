"""Array-native memoizing simulation cache (app x config x region memo).

``MemoBank`` is the cost-accounting heart of the sweep engine: one
``(A, C, N)`` mask + value table covering every (application, config,
region) triple the experiments have paid for. The ledger is charged for
cache *misses only* — the paper's cost unit is "number of 1 M-instruction
region simulations", and a real simulation farm keeps the results it
already paid for. Because the perf model is deterministic, the bank can be
filled by any dispatch path (single app, stacked apps, app-sharded over a
mesh) and later ``merge``-d: device-local banks from a sharded sweep fold
into one table whose charge totals equal a single-host run's.

``CachedSimulator`` keeps the historic per-app surface (``simulate``,
``simulate_cpi``, ``simulate_cpi_batch``) as a one-row view over a
``MemoBank`` — standalone construction gets a private bank; the experiment
engine hands every app a row of its shared bank so one sweep-wide fill is
ONE vmapped (optionally ``shard_map``-ped) dispatch.

Value memoization covers CPI (the sweep/trial hot path). Full-38-metric
requests (``simulate``/``simulate_rfv``) re-run the vectorized perf model
each call — deterministic, so values never change, and NOT re-charged
(the mask is the single source of cost truth) — a deliberate trade: the
bank stays a compact (A, C, N) value table instead of (A, C, N, 38).

``census_stats`` stays analysis-only (free of charge, like the base
simulator) and deliberately does NOT populate the charged memo — otherwise
a census would make every later ``simulate`` call free and the cost
accounting meaningless.
"""

from __future__ import annotations

# jaxlint: disable-file=JL003 — MemoBank's (A, C, N) cpi table is
# float32 storage BY CONTRACT: device mirrors of the table (the fused
# sweep's block cache) must match it bit-for-bit, so the storage dtype
# is part of the memo contract, not a PrecisionPolicy leak.

from typing import Optional, Sequence

import numpy as np

from ..core.features import build_rfv
from .perfmodel import cpi_bank, evaluate_regions_batch
from .simulator import CycleAccurateSimulator, Ledger
from .uarch import UarchConfig
from .workload import get_population


class MemoBank:
    """Growable ``(A, C, N)`` mask + CPI-value memo with per-app ledgers."""

    def __init__(self):
        self.names: list[str] = []
        self.ledgers: list[Optional[Ledger]] = []
        self.n_regions: list[int] = []
        self.hit_count: list[int] = []     # per-app requested-and-cached units
        self.miss_count: list[int] = []    # per-app newly-charged units
        self._cfg_cols: dict[UarchConfig, int] = {}
        self.configs: list[UarchConfig] = []
        self.mask = np.zeros((0, 0, 0), bool)         # (A, C, N)
        self.cpi = np.zeros((0, 0, 0), np.float32)    # (A, C, N)
        self.charges = np.zeros((0, 0), np.int64)     # (A, C) miss counts
        # bumped on every mask/cpi table mutation (content or shape);
        # device-resident mirrors of the tables (the fused sweep's block
        # cache) key their validity on it. Code that writes the tables
        # directly — test/bench snapshot-restore helpers — must call
        # ``touch()``.
        self.version = 0
        # column-granularity reuse bookkeeping for the serving-path
        # eviction policy: last-use tick per column (LRU order) and the
        # host-spill store of evicted-with-spill columns
        self._col_tick: dict[int, int] = {}
        self._lru_clock = 0
        self._spill: dict[int, tuple[np.ndarray, np.ndarray]] = {}

    # -- shape management ---------------------------------------------------
    @property
    def num_apps(self) -> int:
        return len(self.names)

    def touch(self) -> None:
        """Invalidate device-resident mirrors after direct table writes.

        Every mutating method bumps ``version`` itself; only code that
        assigns into ``mask``/``cpi`` directly (snapshot-restore helpers
        in tests and benches) needs to call this."""
        self.version += 1

    def _grow(self, a: int, c: int, n: int) -> None:
        a0, c0, n0 = self.mask.shape
        if (a, c, n) == (a0, c0, n0):
            return
        mask = np.zeros((a, c, n), bool)
        cpi = np.zeros((a, c, n), np.float32)
        charges = np.zeros((a, c), np.int64)
        mask[:a0, :c0, :n0] = self.mask
        cpi[:a0, :c0, :n0] = self.cpi
        charges[:a0, :c0] = self.charges
        self.mask, self.cpi, self.charges = mask, cpi, charges
        self.version += 1

    def add_app(self, name: str, n_regions: int,
                ledger: Optional[Ledger] = None) -> int:
        """Register an app row; returns its row index."""
        row = len(self.names)
        self.names.append(name)
        self.ledgers.append(ledger)
        self.n_regions.append(int(n_regions))
        self.hit_count.append(0)
        self.miss_count.append(0)
        a0, c0, n0 = self.mask.shape
        self._grow(row + 1, c0, max(n0, int(n_regions)))
        return row

    def cols_for(self, cfgs: Sequence[UarchConfig]) -> np.ndarray:
        """Column indices for configs, growing the config axis as needed.

        This is the single column-resolution chokepoint every fill/
        checkout path routes through, so it doubles as the eviction
        policy's touch point: each resolved column's last-use tick
        advances (LRU order for ``evict_to_cap``), and columns that were
        ``spill``-ed restore transparently from the host spill store —
        a free operation (values were already paid for), so ledger
        totals match a never-spilled run.
        """
        for cfg in cfgs:
            if cfg not in self._cfg_cols:
                self._cfg_cols[cfg] = len(self.configs)
                self.configs.append(cfg)
        a0, c0, n0 = self.mask.shape
        self._grow(a0, len(self.configs), n0)
        cols = [self._cfg_cols[c] for c in cfgs]
        self._lru_clock += 1
        for c in cols:
            self._col_tick[c] = self._lru_clock
            if c in self._spill:
                self._unspill(c)
        return np.asarray(cols, np.int64)

    # -- eviction / host spill (the serving-path residency policy) -----------
    def _unspill(self, col: int) -> None:
        """Restore one spilled column into the live tables (free)."""
        mask_c, cpi_c = self._spill.pop(col)
        a, n = mask_c.shape
        self.mask[:a, col, :n] = mask_c
        self.cpi[:a, col, :n] = cpi_c
        self.version += 1

    def resident_columns(self) -> list[int]:
        """Config columns currently holding memo data in the live tables
        (spilled/evicted columns are not resident until re-requested)."""
        return [c for c in range(len(self.configs))
                if c not in self._spill and bool(self.mask[:, c, :].any())]

    def evict(self, cols: Sequence[int], *, spill: bool = False) -> None:
        """Drop the given config columns from the live tables.

        ``spill=False`` discards the data: a later request for an
        evicted config is a miss again and is RE-CHARGED (exactly once —
        the refill repopulates the mask like any first fill). With
        ``spill=True`` the column's mask/value data moves to a host
        spill store instead; ``cols_for`` restores it transparently on
        the next request, free of charge, so ledger totals equal a
        never-evicted run. Either way ``version`` bumps, invalidating
        every device-resident block mirror (the fused sweep's
        ``_BLOCK_CACHE``) — no stale-block reuse.
        """
        cols = [int(c) for c in cols]
        for c in cols:
            if c in self._spill:
                continue                       # already spilled: no-op
            if spill:
                self._spill[c] = (self.mask[:, c, :].copy(),
                                  self.cpi[:, c, :].copy())
            # charges stay: they are the cumulative cost HISTORY (ledger
            # totals never roll back); a re-request of a dropped column
            # adds its refill misses on top, exactly like a first fill
            self.mask[:, c, :] = False
            self.cpi[:, c, :] = 0.0
            self._col_tick.pop(c, None)
        if cols:
            self.version += 1

    def spill(self, cols: Sequence[int]) -> None:
        """``evict`` with host spill: data parks off the live tables and
        restores free on the next request (see ``evict``)."""
        self.evict(cols, spill=True)

    def evict_to_cap(self, cap: int, *, policy: str = "lru",
                     spill: bool = False) -> list[int]:
        """Evict/spill columns until at most ``cap`` remain resident.

        ``policy="lru"`` drops least-recently-used columns first;
        ``policy="charge"`` drops the cheapest-to-recompute first
        (lowest accumulated charge, LRU tie-break) — the charge-weighted
        option for banks whose columns cost very different region
        counts. Returns the evicted column indices (empty when already
        under cap).
        """
        if policy not in ("lru", "charge"):
            raise ValueError(f"unknown eviction policy {policy!r}; "
                             "choose 'lru' or 'charge'")
        resident = self.resident_columns()
        if cap < 0 or len(resident) <= cap:
            return []
        if policy == "charge":
            order = sorted(resident,
                           key=lambda c: (int(self.charges[:, c].sum()),
                                          self._col_tick.get(c, 0)))
        else:
            order = sorted(resident, key=lambda c: self._col_tick.get(c, 0))
        victims = order[:len(resident) - cap]
        self.evict(victims, spill=spill)
        return victims

    # -- the one batched fill path ------------------------------------------
    def fill(self, rows, idx, valid, cfgs: Sequence[UarchConfig], *,
             feats=None, values=None, mesh=None
             ) -> tuple[np.ndarray, np.ndarray]:
        """Serve ``(R, C, K)`` CPI through the memo; charge misses only.

        ``rows``: (R,) app rows; ``idx``: (R, K) region indices (padding
        allowed, flagged invalid in ``valid``); ``feats``: (R, K, F)
        gathered features, or a callable that returns them, called only
        when a requested region misses — evaluated in ONE vmapped
        dispatch (app-sharded when ``mesh`` is given) — or ``values``:
        (R, C, K) precomputed CPI (full-stats path). Returns ``(cpi,
        n_miss)`` with ``n_miss`` the per-(row, config) newly-charged
        region counts.
        """
        rows = np.asarray(rows, np.int64)
        idx = np.asarray(idx, np.int64)
        valid = np.ones(idx.shape, bool) if valid is None \
            else np.asarray(valid, bool)
        cols = self.cols_for(cfgs)
        n = self.mask.shape[2]
        r_n, k = idx.shape
        c_n = cols.size
        sub = (rows[:, None], cols[None, :])

        req = np.zeros((r_n, n), bool)
        rr = np.broadcast_to(np.arange(r_n)[:, None], idx.shape)
        req[rr[valid], idx[valid]] = True
        miss = req[:, None, :] & ~self.mask[sub]          # (R, C, N)
        n_miss = miss.sum(axis=2)                          # (R, C)
        requested = valid.sum(axis=1) * c_n                # (R,) incl. dups
        for i, row in enumerate(rows.tolist()):
            self.miss_count[row] += int(n_miss[i].sum())
            self.hit_count[row] += int(requested[i] - n_miss[i].sum())

        if not n_miss.any():                               # fully memoized
            out = np.take_along_axis(self.cpi[sub],
                                     np.broadcast_to(idx[:, None, :],
                                                     (r_n, c_n, k)), axis=2)
            return out, n_miss

        if values is None:
            if callable(feats):
                feats = feats()
            values = cpi_bank(feats, cfgs, mesh=mesh)      # (R, C, K)
        values = np.asarray(values, np.float32)

        # scatter valid entries into dense (R, C, N), then write misses only
        dense = np.zeros((r_n, c_n, n), np.float32)
        r3 = np.broadcast_to(np.arange(r_n)[:, None, None], values.shape)
        c3 = np.broadcast_to(np.arange(c_n)[None, :, None], values.shape)
        i3 = np.broadcast_to(idx[:, None, :], values.shape)
        v3 = np.broadcast_to(valid[:, None, :], values.shape)
        dense[r3[v3], c3[v3], i3[v3]] = values[v3]
        blk = self.cpi[sub]
        self.cpi[sub] = np.where(miss, dense, blk)
        self.mask[sub] |= miss
        self.charges[sub] += n_miss
        self.version += 1
        for i, row in enumerate(rows.tolist()):
            ledger = self.ledgers[row]
            if ledger is not None:
                ledger.charge(int(n_miss[i].sum()))
        out = np.take_along_axis(self.cpi[sub],
                                 np.broadcast_to(idx[:, None, :],
                                                 (r_n, c_n, k)), axis=2)
        return out, n_miss

    # -- donated-buffer fused-fill contract ----------------------------------
    def donation_block(self, rows, cfgs: Sequence[UarchConfig]
                       ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(mask, cpi, cols) block for a donated fused sweep dispatch.

        Extracts the ``(R, C, N)`` mask + value sub-block the fused
        program (``repro.experiments.fused``) consumes as DONATED device
        buffers: ownership transfers to the program — the caller must
        not read these arrays after dispatch — and the updated block
        comes back through ``absorb_block``. Fancy indexing copies, so
        the bank's own tables are never aliased by donated memory.
        """
        rows = np.asarray(rows, np.int64)
        cols = self.cols_for(cfgs)
        sub = (rows[:, None], cols[None, :])
        return self.mask[sub], self.cpi[sub], cols

    def absorb_block(self, rows, cols, new_mask, new_cpi, n_miss,
                     requested) -> None:
        """Write back a fused program's updated block; account as
        ``fill`` would, bitwise.

        ``new_mask``/``new_cpi``: the (R, C, N) block returned by the
        fused program (old block ∪ misses); ``n_miss``: its (R, C)
        newly-charged counts; ``requested``: (R,) requested region-units
        per row INCLUDING duplicates times configs (``fill``'s
        ``valid.sum(axis=1) * C`` convention). Hit/miss counters, the
        charge matrix and the per-app ledgers advance exactly as one
        equivalent ``fill`` call — ledger totals are path-independent.
        """
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        sub = (rows[:, None], cols[None, :])
        n_miss = np.asarray(n_miss, np.int64)
        requested = np.asarray(requested, np.int64)
        self.cpi[sub] = np.asarray(new_cpi, np.float32)
        self.mask[sub] = np.asarray(new_mask, bool)
        self.charges[sub] += n_miss
        self.version += 1
        for i, row in enumerate(rows.tolist()):
            row_miss = int(n_miss[i].sum())
            self.miss_count[row] += row_miss
            self.hit_count[row] += int(requested[i]) - row_miss
            ledger = self.ledgers[row]
            if ledger is not None and row_miss:
                ledger.charge(row_miss)

    def absorb_selected(self, rows, cols, picks, miss_sel, values, n_miss,
                        requested) -> None:
        """Write back a fused program's selected-unit results; account as
        ``fill`` would, bitwise — without an (R, C, N) block transfer.

        ``picks``: (R, K) picked region indices; ``miss_sel``: (R, C, K)
        True where the pick was newly computed (invalid strata already
        False); ``values``: (R, C, K) CPI at the picks (stored on hits,
        freshly computed on misses) — only missed cells are written, so
        the host tables end up identical to an ``absorb_block`` of the
        full updated block. ``n_miss``/``requested`` follow the
        ``absorb_block`` conventions (dedup-exact counts from the
        program's dense request scatter).
        """
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        picks = np.asarray(picks, np.int64)
        miss_sel = np.asarray(miss_sel, bool)
        values = np.asarray(values, np.float32)
        n_miss = np.asarray(n_miss, np.int64)
        requested = np.asarray(requested, np.int64)
        r3 = np.broadcast_to(rows[:, None, None], miss_sel.shape)
        c3 = np.broadcast_to(cols[None, :, None], miss_sel.shape)
        i3 = np.broadcast_to(picks[:, None, :], miss_sel.shape)
        if miss_sel.any():
            self.cpi[r3[miss_sel], c3[miss_sel], i3[miss_sel]] = \
                values[miss_sel]
            self.mask[r3[miss_sel], c3[miss_sel], i3[miss_sel]] = True
            self.version += 1
        sub = (rows[:, None], cols[None, :])
        self.charges[sub] += n_miss
        for i, row in enumerate(rows.tolist()):
            row_miss = int(n_miss[i].sum())
            self.miss_count[row] += row_miss
            self.hit_count[row] += int(requested[i]) - row_miss
            ledger = self.ledgers[row]
            if ledger is not None and row_miss:
                ledger.charge(row_miss)

    def absorb_picks(self, rows, cols, picks, valid, values) -> np.ndarray:
        """Absorb one request's selected-unit results, recomputing its
        miss flags against the CURRENT host tables.

        The coalescing batcher (``repro.serving``) stacks many requests
        into one fused dispatch; the program's in-trace miss counts are
        computed per request against the shared PRE-dispatch block, so
        two coalesced requests touching the same cold cell would each
        count it as a miss. This method restores serial accounting:
        called once per request in submission order, it re-derives the
        dense dedup-exact request scatter (``fill``'s convention)
        against the tables as the EARLIER requests left them, then
        delegates to ``absorb_selected`` — so charges, hit/miss counters
        and ledger totals land bitwise-identical to the same requests
        run serially. ``values`` holds the request's (R, C, K) selected
        CPI (stored on hits, computed on misses — bitwise equal either
        way for same-program lanes); only newly-missed cells are
        written. Returns the (R, C) per-request miss counts.
        """
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        picks = np.asarray(picks, np.int64)
        valid = np.asarray(valid, bool)
        r_n, k = picks.shape
        c_n = cols.size
        n = self.mask.shape[2]
        sub = (rows[:, None], cols[None, :])
        picks_b = np.broadcast_to(picks[:, None, :], (r_n, c_n, k))
        hit_sel = np.take_along_axis(self.mask[sub], picks_b, axis=2)
        if bool((hit_sel | ~valid[:, None, :]).all()):
            # warm fast path: every valid pick is already present. The
            # dense request scatter only marks picked regions, so zero
            # selected misses means zero misses anywhere — skip the
            # (R, C, N) materialization; the accounting below is
            # bitwise what the dense path would produce.
            n_miss = np.zeros((r_n, c_n), np.int64)
            self.absorb_selected(rows, cols, picks,
                                 np.zeros((r_n, c_n, k), bool), values,
                                 n_miss, requested=valid.sum(axis=1) * c_n)
            return n_miss
        req = np.zeros((r_n, n), bool)
        rr = np.broadcast_to(np.arange(r_n)[:, None], picks.shape)
        req[rr[valid], picks[valid]] = True
        miss = req[:, None, :] & ~self.mask[sub]            # (R, C, N)
        n_miss = miss.sum(axis=2)
        miss_sel = np.take_along_axis(miss, picks_b, axis=2) \
            & valid[:, None, :]
        self.absorb_selected(rows, cols, picks, miss_sel, values, n_miss,
                             requested=valid.sum(axis=1) * c_n)
        return n_miss

    # -- snapshot / restore (the checkpointed-fleet contract) ----------------
    def state(self) -> tuple[dict, dict]:
        """``(tree, meta)`` snapshot of the bank's full mutable state.

        ``tree`` is a checkpointable array pytree — mask + CPI tables,
        the charge matrix, hit/miss counters, per-app ledger totals and
        the ``version`` counter; ``meta`` is the JSON-able identity
        (app names, region counts, config reprs — ``UarchConfig`` reprs
        are unique via their ``name`` field) a restore validates and
        resolves columns against. Restoring ``state()`` into an
        identically-built bank reproduces every later fill bitwise,
        including the cost accounting. Spilled columns are restored into
        the live tables first so the snapshot always carries the full
        memo content (the spill store itself is not serialized).
        """
        for col in sorted(self._spill):
            self._unspill(col)
        regions = [0 if l is None else int(l.regions_simulated)
                   for l in self.ledgers]
        instr = [0 if l is None else int(l.instructions_simulated)
                 for l in self.ledgers]
        tree = {
            "mask": self.mask.copy(),
            "cpi": self.cpi.copy(),
            "charges": self.charges.copy(),
            "hit_count": np.asarray(self.hit_count, np.int64),
            "miss_count": np.asarray(self.miss_count, np.int64),
            "ledger_regions": np.asarray(regions, np.int64),
            "ledger_instr": np.asarray(instr, np.int64),
            "version": np.asarray(self.version, np.int64),
        }
        meta = {"names": list(self.names),
                "n_regions": [int(n) for n in self.n_regions],
                "configs": [repr(c) for c in self.configs]}
        return tree, meta

    def prepare_restore(self, meta: dict, *, universe: Sequence = ()
                        ) -> np.ndarray:
        """Validate a snapshot's identity against this bank and align the
        config axis: grows columns so every snapshot config has a local
        column (objects resolved by repr from ``universe`` + the bank's
        own configs). Returns the (C_snapshot,) local column index per
        snapshot column. Raises ``ValueError`` on any identity drift —
        app set, region counts, unknown configs, or local columns the
        snapshot does not cover (their state would be inconsistent)."""
        if list(meta["names"]) != self.names:
            raise ValueError(
                f"memobank snapshot is for apps {meta['names']}, "
                f"this bank holds {self.names}")
        if [int(n) for n in meta["n_regions"]] != \
                [int(n) for n in self.n_regions]:
            raise ValueError("memobank snapshot region counts differ")
        by_repr = {repr(c): c for c in list(self.configs) + list(universe)}
        missing = [r for r in meta["configs"] if r not in by_repr]
        if missing:
            raise ValueError(
                f"snapshot configs not resolvable from the given universe:"
                f" {missing}")
        snap = set(meta["configs"])
        extra = [repr(c) for c in self.configs if repr(c) not in snap]
        if extra:
            raise ValueError(
                f"bank holds config columns the snapshot does not cover "
                f"(restore would leave them inconsistent): {extra}")
        return self.cols_for([by_repr[r] for r in meta["configs"]])

    def load_state(self, tree: dict, meta: dict, *,
                   universe: Sequence = ()) -> None:
        """Overwrite this bank's state with a ``state()`` snapshot.

        The bank must hold the same apps (a deterministic engine rebuild
        does); config columns may be fewer or permuted — they are grown/
        aligned via ``prepare_restore``. Every piece of cost accounting
        (charges, hit/miss counters, ledger totals) is REPLACED by the
        snapshot's, so re-fills performed since construction (e.g. the
        engine's phase-1 build fill, re-charged on restart) are not
        double-counted. ``version`` restores exactly as saved.
        """
        cols = self.prepare_restore(meta, universe=universe)
        # the snapshot carries the full live tables; stale spill entries
        # must not "restore" over them later
        self._spill.clear()
        self.mask[:, cols, :] = np.asarray(tree["mask"], bool)
        self.cpi[:, cols, :] = np.asarray(tree["cpi"], np.float32)
        self.charges[:, cols] = np.asarray(tree["charges"], np.int64)
        self.hit_count = [int(x) for x in np.asarray(tree["hit_count"])]
        self.miss_count = [int(x) for x in np.asarray(tree["miss_count"])]
        regions = np.asarray(tree["ledger_regions"])
        instr = np.asarray(tree["ledger_instr"])
        for i, ledger in enumerate(self.ledgers):
            if ledger is not None:
                ledger.regions_simulated = int(regions[i])
                ledger.instructions_simulated = int(instr[i])
        # version restores exactly in the fresh-rebuild case (the bank
        # never reached the saved version, so no device-resident mirror
        # can be stamped with it); rolling BACK a bank that already
        # advanced past the snapshot must instead move forward, or a
        # stale fused-block mirror stamped at the saved version would
        # revalidate against different table contents
        saved = int(np.asarray(tree["version"]))
        self.version = saved if saved >= self.version else self.version + 1

    # -- cross-device merge --------------------------------------------------
    def merge(self, other: "MemoBank") -> None:
        """Fold a device-local bank into this one.

        Apps/configs unknown here are added. Values for entries both banks
        hold agree by determinism; charges ADD (each device paid for its
        own misses), so merged ledger totals equal a single-host run's when
        the work was partitioned disjointly.

        Apps the banks share must agree on their region counts — two
        rows with the same name but different populations are different
        app universes, and merging them would corrupt both tables.
        Mismatches raise ``ValueError`` naming the offending apps
        instead of surfacing as an indexing shape error deep in numpy.
        """
        mismatched = [
            (name, self.n_regions[self.names.index(name)], int(n_reg))
            for name, n_reg in zip(other.names, other.n_regions)
            if name in self.names
            and self.n_regions[self.names.index(name)] != int(n_reg)]
        if mismatched:
            detail = ", ".join(f"{name!r} ({mine} regions here, {theirs} "
                               "in the other bank)"
                               for name, mine, theirs in mismatched)
            raise ValueError(
                "cannot merge MemoBanks with mismatched app universes: "
                + detail)
        for col in sorted(other._spill):
            other._unspill(col)
        for col in sorted(self._spill):
            self._unspill(col)
        row_map = []
        for name, n_reg in zip(other.names, other.n_regions):
            if name in self.names:
                row_map.append(self.names.index(name))
            else:
                row_map.append(self.add_app(name, n_reg, Ledger()))
        cols = self.cols_for(other.configs)
        n_other = other.mask.shape[2]
        for i, row in enumerate(row_map):
            om = other.mask[i]                  # (C_other, N_other)
            sl = (row, cols[:, None], np.arange(n_other)[None, :])
            new = om & ~self.mask[sl]
            self.cpi[sl] = np.where(new, other.cpi[i], self.cpi[sl])
            self.mask[sl] |= om
            self.version += 1
            self.charges[row, cols] += other.charges[i]
            self.hit_count[row] += other.hit_count[i]
            self.miss_count[row] += other.miss_count[i]
            ledger = self.ledgers[row]
            if ledger is not None:
                ledger.charge(int(other.charges[i].sum()))

    def total_charges(self) -> int:
        return int(self.charges.sum())


class CachedSimulator:
    """``CycleAccurateSimulator`` with an app-row view over a ``MemoBank``.

    Same interface as the base simulator; the ledger is charged only for
    cache *misses*. ``hits`` / ``misses`` count requested region-units
    served from / added to the memo.
    """

    def __init__(self, sim: CycleAccurateSimulator, *,
                 bank: Optional[MemoBank] = None, row: Optional[int] = None):
        self.sim = sim
        if bank is None:
            bank = MemoBank()
            row = bank.add_app(sim.pop.spec.name, sim.pop.n_regions,
                               sim.ledger)
        self.bank = bank
        self.row = int(row)

    # hit/miss accounting lives on the bank so engine-level stacked fills
    # are reflected in every app view
    @property
    def hits(self) -> int:
        return self.bank.hit_count[self.row]

    @property
    def misses(self) -> int:
        return self.bank.miss_count[self.row]

    # base-simulator surface -------------------------------------------------
    @property
    def pop(self):
        return self.sim.pop

    @property
    def ledger(self) -> Ledger:
        return self.sim.ledger

    def _fill(self, idx: np.ndarray, cfgs: Sequence[UarchConfig],
              values=None) -> np.ndarray:
        feats = None if values is not None else \
            self.pop.features[idx][None].astype(np.float32)
        cpi, _ = self.bank.fill(
            np.asarray([self.row]), idx[None, :], None, cfgs,
            feats=feats, values=values)
        return cpi[0]

    def simulate(self, indices, cfg: UarchConfig) -> dict[str, np.ndarray]:
        """All 38 Table III counters; CPI memoized, misses charged once."""
        idx = np.atleast_1d(np.asarray(indices, np.int64))
        stats = evaluate_regions_batch(self.pop.features, (cfg,), idx)
        stats = {m: v[0] for m, v in stats.items()}
        self._fill(idx, (cfg,), values=stats["cpi"][None, None, :])
        return stats

    def simulate_cpi(self, indices, cfg: UarchConfig) -> np.ndarray:
        idx = np.atleast_1d(np.asarray(indices, np.int64))
        return self._fill(idx, (cfg,))[0]

    def simulate_rfv(self, indices, cfg: UarchConfig
                     ) -> tuple[np.ndarray, np.ndarray]:
        stats = self.simulate(indices, cfg)
        return stats["cpi"], build_rfv(stats)

    # batched surface (the experiment engine's hot path) ---------------------
    def simulate_batch(self, indices, cfgs: Sequence[UarchConfig]
                       ) -> dict[str, np.ndarray]:
        """Metric dict of (C, n) matrices for ``indices`` across ``cfgs``,
        evaluated in one vmapped dispatch; misses charged per config."""
        idx = np.atleast_1d(np.asarray(indices, np.int64))
        stats = evaluate_regions_batch(self.pop.features, cfgs, idx)
        self._fill(idx, tuple(cfgs), values=stats["cpi"][None])
        return stats

    def simulate_cpi_batch(self, indices, cfgs: Sequence[UarchConfig]
                           ) -> np.ndarray:
        idx = np.atleast_1d(np.asarray(indices, np.int64))
        return self._fill(idx, tuple(cfgs))

    # -- ground truth (free of charge, never touches the charged memo) ------
    def census_stats(self, cfg: UarchConfig) -> dict[str, np.ndarray]:
        return self.sim.census_stats(cfg)

    def true_mean_cpi(self, cfg: UarchConfig) -> float:
        return self.sim.true_mean_cpi(cfg)


def make_cached_simulator(app_name: str, *, seed: int = 0,
                          ledger: Optional[Ledger] = None,
                          bank: Optional[MemoBank] = None,
                          row: Optional[int] = None) -> CachedSimulator:
    sim = CycleAccurateSimulator(get_population(app_name, seed=seed), ledger)
    return CachedSimulator(sim, bank=bank, row=row)
