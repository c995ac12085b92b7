"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode).

Property tests live in test_properties.py (they need hypothesis and
skip cleanly when it is absent).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Eq, Query, Range, SortedTable
from repro.kernels import (
    device_key_plan,
    ecdf_hist,
    ecdf_hist_ref,
    scan_agg,
    scan_agg_batched,
    scan_agg_batched_ref,
    scan_agg_ref,
    table_scan_device,
    table_scan_device_many,
)

pytestmark = pytest.mark.kernel


class TestScanAgg:
    @pytest.mark.parametrize("K", [1, 2, 3, 5, 8, 11])
    @pytest.mark.parametrize("N", [1, 100, 2048, 5000])
    def test_shape_sweep(self, rng, K, N):
        keys = rng.integers(0, 64, (K, N)).astype(np.int32)
        vals = rng.uniform(-2, 2, N).astype(np.float32)
        lo = rng.integers(0, 32, K).astype(np.int32)
        hi = (lo + rng.integers(1, 32, K)).astype(np.int32)
        slab = np.sort(rng.integers(0, N + 1, 2)).astype(np.int32)
        got = np.asarray(scan_agg(keys, vals, lo, hi, slab, block_n=512))
        want = np.asarray(
            scan_agg_ref(jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(lo),
                         jnp.asarray(hi), jnp.asarray(slab))
        )
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)

    @pytest.mark.parametrize("block_n", [128, 256, 2048])
    def test_block_size_invariance(self, rng, block_n):
        keys = rng.integers(0, 16, (3, 3000)).astype(np.int32)
        vals = rng.uniform(0, 1, 3000).astype(np.float32)
        lo = np.zeros(3, np.int32)
        hi = np.full(3, 8, np.int32)
        slab = np.array([100, 2900], np.int32)
        a = np.asarray(scan_agg(keys, vals, lo, hi, slab, block_n=block_n))
        b = np.asarray(scan_agg(keys, vals, lo, hi, slab, block_n=1024))
        np.testing.assert_allclose(a, b, rtol=1e-6)

    def test_value_dtypes(self, rng):
        keys = rng.integers(0, 8, (2, 1000)).astype(np.int32)
        lo = np.zeros(2, np.int32); hi = np.full(2, 4, np.int32)
        slab = np.array([0, 1000], np.int32)
        for dt in (np.float32, np.float64, np.int32):
            vals = rng.integers(0, 5, 1000).astype(dt)
            got = np.asarray(scan_agg(keys, vals, lo, hi, slab))
            want = np.asarray(
                scan_agg_ref(jnp.asarray(keys), jnp.asarray(vals, dtype=jnp.float32),
                             jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(slab))
            )
            np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_empty_slab(self, rng):
        keys = rng.integers(0, 8, (2, 512)).astype(np.int32)
        vals = rng.uniform(0, 1, 512).astype(np.float32)
        got = np.asarray(scan_agg(keys, vals, np.zeros(2, np.int32),
                                  np.full(2, 8, np.int32), np.array([7, 7], np.int32)))
        assert got[0] == 0 and got[1] == 0

    def test_matches_table_engine(self, rng):
        kc = {"a": rng.integers(0, 30, 4000), "b": rng.integers(0, 30, 4000)}
        vc = {"m": rng.uniform(0, 5, 4000)}
        t = SortedTable.from_columns(kc, vc, ("b", "a"))
        for _ in range(5):
            q = Query(
                filters={"a": Range(int(rng.integers(0, 15)), int(rng.integers(15, 30))),
                         "b": Eq(int(rng.integers(0, 30)))},
                agg="sum", value_col="m",
            )
            dev_val, dev_cnt = table_scan_device(t, q)
            res = t.execute(q)
            assert dev_cnt == res.rows_matched
            np.testing.assert_allclose(dev_val, res.value, rtol=1e-4, atol=1e-3)


class TestScanAggBatched:
    @pytest.mark.parametrize("K", [1, 3, 8])
    @pytest.mark.parametrize("Q", [1, 5, 17])
    @pytest.mark.parametrize("N", [1, 100, 2048, 5000])
    def test_shape_sweep_vs_ref(self, rng, K, Q, N):
        keys = rng.integers(0, 64, (K, N)).astype(np.int32)
        vals = rng.uniform(-2, 2, N).astype(np.float32)
        lo = rng.integers(0, 32, (Q, K)).astype(np.int32)
        hi = (lo + rng.integers(1, 32, (Q, K))).astype(np.int32)
        slabs = np.sort(rng.integers(0, N + 1, (Q, 2)), axis=1).astype(np.int32)
        got = np.asarray(scan_agg_batched(keys, vals, lo, hi, slabs, block_n=512))
        want = np.asarray(
            scan_agg_batched_ref(jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(lo),
                                 jnp.asarray(hi), jnp.asarray(slabs))
        )
        assert got.shape == (Q, 2)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)

    @pytest.mark.parametrize("block_n", [128, 256, 2048])
    def test_block_size_invariance(self, rng, block_n):
        keys = rng.integers(0, 16, (3, 3000)).astype(np.int32)
        vals = rng.uniform(0, 1, 3000).astype(np.float32)
        lo = rng.integers(0, 8, (9, 3)).astype(np.int32)
        hi = (lo + rng.integers(1, 8, (9, 3))).astype(np.int32)
        slabs = np.sort(rng.integers(0, 3001, (9, 2)), axis=1).astype(np.int32)
        a = np.asarray(scan_agg_batched(keys, vals, lo, hi, slabs, block_n=block_n))
        b = np.asarray(scan_agg_batched(keys, vals, lo, hi, slabs, block_n=1024))
        np.testing.assert_allclose(a, b, rtol=1e-6)

    def test_matches_unbatched_kernel_per_query(self, rng):
        keys = rng.integers(0, 32, (4, 2500)).astype(np.int32)
        vals = rng.uniform(-1, 1, 2500).astype(np.float32)
        lo = rng.integers(0, 16, (6, 4)).astype(np.int32)
        hi = (lo + rng.integers(1, 16, (6, 4))).astype(np.int32)
        slabs = np.sort(rng.integers(0, 2501, (6, 2)), axis=1).astype(np.int32)
        batched = np.asarray(scan_agg_batched(keys, vals, lo, hi, slabs, block_n=512))
        for q in range(6):
            single = np.asarray(
                scan_agg(keys, vals, lo[q], hi[q], slabs[q], block_n=512)
            )
            np.testing.assert_allclose(batched[q], single, rtol=1e-5, atol=1e-3)

    def test_empty_slabs(self, rng):
        keys = rng.integers(0, 8, (2, 512)).astype(np.int32)
        vals = rng.uniform(0, 1, 512).astype(np.float32)
        lo = np.zeros((3, 2), np.int32)
        hi = np.full((3, 2), 8, np.int32)
        slabs = np.array([[7, 7], [0, 0], [512, 512]], np.int32)
        got = np.asarray(scan_agg_batched(keys, vals, lo, hi, slabs))
        np.testing.assert_array_equal(got, 0.0)

    def test_table_scan_device_many_matches_engine(self, rng):
        kc = {"a": rng.integers(0, 30, 4000), "b": rng.integers(0, 30, 4000)}
        vc = {"m": rng.uniform(0, 5, 4000)}
        t = SortedTable.from_columns(kc, vc, ("b", "a"))
        queries = [
            Query(
                filters={"a": Range(int(rng.integers(0, 15)), int(rng.integers(15, 30))),
                         "b": Eq(int(rng.integers(0, 30)))},
                agg="sum", value_col="m",
            )
            for _ in range(8)
        ]
        dev = table_scan_device_many(t, queries)
        for q, (dev_val, dev_cnt) in zip(queries, dev):
            res = t.execute(q)
            assert dev_cnt == res.rows_matched
            np.testing.assert_allclose(dev_val, res.value, rtol=1e-4, atol=1e-3)

    def test_mixed_agg_batch_one_launch(self, rng):
        """Sum queries over different value columns and count queries
        ride one launch (multi-row value tile + per-query selector)."""
        kc = {"a": rng.integers(0, 8, 3000), "b": rng.integers(0, 8, 3000)}
        vc = {"m": rng.uniform(0, 1, 3000), "w": rng.uniform(-2, 2, 3000)}
        t = SortedTable.from_columns(kc, vc, ("a", "b"))
        qs = [Query(filters={"a": Eq(1)}, agg="count"),
              Query(filters={"a": Eq(2)}, agg="sum", value_col="m"),
              Query(filters={"b": Range(1, 6)}, agg="sum", value_col="w"),
              Query(filters={"b": Eq(3)}, agg="sum", value_col="m"),
              Query(filters={}, agg="count")]
        dev = table_scan_device_many(t, qs)
        for q, (dev_val, dev_cnt) in zip(qs, dev):
            res = t.execute(q)
            assert dev_cnt == res.rows_matched
            np.testing.assert_allclose(dev_val, res.value, rtol=1e-4, atol=1e-3)

    def test_select_agg_rejected(self, rng):
        kc = {"a": rng.integers(0, 8, 100)}
        vc = {"m": rng.uniform(0, 1, 100)}
        t = SortedTable.from_columns(kc, vc, ("a",))
        with pytest.raises(ValueError, match="sum/count"):
            table_scan_device_many(t, [Query(filters={"a": Eq(1)}, agg="select")])
        with pytest.raises(ValueError, match="value_col"):
            table_scan_device_many(t, [Query(filters={"a": Eq(1)}, agg="sum")])

    @pytest.mark.parametrize("bits", [31, 32, 45, 60])
    def test_wide_schema_two_lane_packing(self, rng, bits):
        """Columns wider than one int32 lane (> 30 bits) are split into
        (hi, lo) lane pairs and served on device; 31 bits is the old
        off-by-one rejection case (keys fit int32, the unfiltered
        exclusive bound 2**31 does not)."""
        from repro.core import KeySchema

        schema = KeySchema({"a": bits})
        top = 2**bits
        kc = {"a": rng.integers(top - 8, top, 100).astype(np.int64)}
        vc = {"m": rng.uniform(0, 1, 100)}
        t = SortedTable.from_columns(kc, vc, ("a",), schema)
        assert device_key_plan(t) == (2,)
        qs = [Query(filters={}, agg="count"),
              Query(filters={"a": Eq(int(kc["a"][0]))}, agg="sum", value_col="m"),
              Query(filters={"a": Range(top - 6, top - 2)}, agg="count")]
        dev = table_scan_device_many(t, qs)
        for q, (dev_val, dev_cnt) in zip(qs, dev):
            res = t.execute(q)
            assert dev_cnt == res.rows_matched
            np.testing.assert_allclose(dev_val, res.value, rtol=1e-4, atol=1e-3)

    def test_too_wide_column_rejected_by_name(self, rng):
        """> 60 bits exceeds the two-lane budget: the error names the
        offending column so schema owners know what to shrink."""
        from repro.core import KeySchema

        schema = KeySchema({"ok": 2, "huge": 61})  # 63 bits total
        kc = {"ok": rng.integers(0, 4, 50).astype(np.int64),
              "huge": rng.integers(0, 2**61, 50).astype(np.int64)}
        vc = {"m": rng.uniform(0, 1, 50)}
        t = SortedTable.from_columns(kc, vc, ("ok", "huge"), schema)
        q = Query(filters={}, agg="count")
        with pytest.raises(ValueError, match="'huge'"):
            table_scan_device(t, q)
        with pytest.raises(ValueError, match="60-bit"):
            table_scan_device_many(t, [q])
        with pytest.raises(ValueError, match="'huge'"):
            t.place_on_device()
        # the numpy engine still serves the wide schema
        assert t.execute_many([q])[0].rows_scanned == 50

    @pytest.mark.parametrize("grid", ["rows_outer", "queries_outer"])
    def test_table_scan_ref_fallback_both_grids(self, rng, grid):
        """use_pallas=False must serve either grid via the shared oracle
        (the queries_outer fallback used to crash on the resident keys'
        padded sublanes)."""
        kc = {"a": rng.integers(0, 16, 500)}
        vc = {"m": rng.uniform(0, 1, 500)}
        t = SortedTable.from_columns(kc, vc, ("a",))
        qs = [Query(filters={"a": Eq(int(rng.integers(0, 16)))},
                    agg="sum", value_col="m") for _ in range(4)]
        got = table_scan_device_many(t, qs, use_pallas=False, grid=grid)
        for q, (val, cnt) in zip(qs, got):
            res = t.execute(q)
            assert cnt == res.rows_matched
            np.testing.assert_allclose(val, res.value, rtol=1e-5)

    def test_row_count_cap_lifted_to_int32(self, rng, monkeypatch):
        """Counts now accumulate in int32 lanes: the cap sits at the
        int32 row-index budget (≫ the old float32 2**24), and beyond it
        tables still refuse device placement with a precise error."""
        from repro.kernels import ops

        assert ops.MAX_DEVICE_ROWS > (1 << 24)  # the old cap is lifted
        kc = {"a": rng.integers(0, 16, 100)}
        vc = {"m": rng.uniform(0, 1, 100)}
        t = SortedTable.from_columns(kc, vc, ("a",))
        monkeypatch.setattr(ops, "MAX_DEVICE_ROWS", 64)
        with pytest.raises(ValueError, match="int32 row"):
            t.place_on_device()
        with pytest.raises(ValueError, match="numpy engine"):
            table_scan_device_many(t, [Query(filters={}, agg="count")])
        # appends respect the cap too
        monkeypatch.setattr(ops, "MAX_DEVICE_ROWS", 128)
        t2 = t.place_on_device()
        with pytest.raises(ValueError, match="int32 row"):
            t2.merge_insert({"a": rng.integers(0, 16, 50)}, {"m": np.zeros(50)})
        # the numpy engine still serves it
        assert t.execute_many([Query(filters={}, agg="count")])[0].value == 100.0

    def test_rowstream_matches_qgrid(self, rng):
        """The row-streaming grid and the legacy queries-outer grid are
        the same computation with different HBM traffic."""
        keys = rng.integers(0, 32, (4, 3000)).astype(np.int32)
        vals = rng.uniform(-1, 1, 3000).astype(np.float32)
        lo = rng.integers(0, 16, (9, 4)).astype(np.int32)
        hi = (lo + rng.integers(1, 16, (9, 4))).astype(np.int32)
        slabs = np.sort(rng.integers(0, 3001, (9, 2)), axis=1).astype(np.int32)
        new = np.asarray(scan_agg_batched(keys, vals, lo, hi, slabs, block_n=512))
        old = np.asarray(
            scan_agg_batched(keys, vals, lo, hi, slabs, block_n=512, grid="queries_outer")
        )
        np.testing.assert_allclose(new, old, rtol=1e-5, atol=1e-3)

    def test_value_selector_vs_ref(self, rng):
        """(V, N) value tiles with a per-query row selector."""
        keys = rng.integers(0, 16, (2, 2000)).astype(np.int32)
        vals = rng.uniform(-1, 1, (3, 2000)).astype(np.float32)
        lo = rng.integers(0, 8, (7, 2)).astype(np.int32)
        hi = (lo + rng.integers(1, 8, (7, 2))).astype(np.int32)
        slabs = np.sort(rng.integers(0, 2001, (7, 2)), axis=1).astype(np.int32)
        sel = rng.integers(0, 3, 7).astype(np.int32)
        got = np.asarray(scan_agg_batched(keys, vals, lo, hi, slabs, sel, block_n=256))
        want = np.asarray(
            scan_agg_batched_ref(
                jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(lo),
                jnp.asarray(hi), jnp.asarray(slabs), jnp.asarray(sel),
            )
        )
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)

    def test_batch_chunking_matches_single_launch(self, rng):
        """Batches beyond max_q are chunked; results are unchanged."""
        from repro.kernels.scan_agg import scan_agg_batched_pallas

        keys = rng.integers(0, 16, (2, 1000)).astype(np.int32)
        vals = rng.uniform(0, 1, 1000).astype(np.float32)
        lo = rng.integers(0, 8, (21, 2)).astype(np.int32)
        hi = (lo + rng.integers(1, 8, (21, 2))).astype(np.int32)
        slabs = np.sort(rng.integers(0, 1001, (21, 2)), axis=1).astype(np.int32)
        whole = np.asarray(scan_agg_batched_pallas(keys, vals, lo, hi, slabs, block_n=256))
        chunked = np.asarray(
            scan_agg_batched_pallas(keys, vals, lo, hi, slabs, block_n=256, max_q=8)
        )
        np.testing.assert_allclose(whole, chunked, rtol=1e-6)


def _lane_split(v, parts):
    """Split int64 column values into 1 or 2 int32 lanes (test helper
    mirroring the device layout)."""
    from repro.kernels.scan_agg import WIDE_LANE_BITS

    v = np.asarray(v, np.int64)
    if parts == 1:
        return [v.astype(np.int32)]
    mask = (1 << WIDE_LANE_BITS) - 1
    return [(v >> WIDE_LANE_BITS).astype(np.int32), (v & mask).astype(np.int32)]


class TestSlabLocate:
    """slab_locate_batched vs the numpy searchsorted oracle."""

    def _oracle(self, table, queries):
        from repro.core.table import slab_bounds_many

        bounds = slab_bounds_many(queries, table.layout, table.schema)
        lo = np.searchsorted(table.packed, bounds[:, 0], side="left")
        hi = np.searchsorted(table.packed, bounds[:, 1], side="right")
        return np.stack([lo, hi], axis=1).astype(np.int64)

    @pytest.mark.parametrize("bits", [(4, 4), (31, 8), (43, 20), (60, 3)])
    def test_matches_searchsorted_random_schemas(self, rng, bits):
        from repro.core import KeySchema
        from repro.kernels import table_slab_locate_many

        schema = KeySchema({"a": bits[0], "b": bits[1]})
        n = 3000
        kc = {c: rng.integers(0, min(schema.max_value(c) + 1, 2**20), n).astype(np.int64)
              for c in ("a", "b")}
        vc = {"m": rng.uniform(0, 1, n)}
        t = SortedTable.from_columns(kc, vc, ("a", "b"), schema)
        qs = []
        for _ in range(12):
            f = {}
            if rng.random() < 0.7:
                v = int(kc["a"][rng.integers(0, n)])
                f["a"] = Eq(v) if rng.random() < 0.5 else Range(
                    max(0, v - 5), min(schema.max_value("a") + 1, v + 5))
            if rng.random() < 0.5:
                lo = int(rng.integers(0, schema.max_value("b")))
                f["b"] = Range(lo, lo + int(rng.integers(0, 4)))  # may be empty
            qs.append(Query(filters=f))
        dev = t.place_on_device()
        np.testing.assert_array_equal(table_slab_locate_many(dev, qs), self._oracle(t, qs))
        # ref oracle path agrees too
        np.testing.assert_array_equal(
            table_slab_locate_many(dev, qs, use_pallas=False), self._oracle(t, qs)
        )

    def test_bounds_at_table_edges(self, rng):
        """Slabs clamped at row 0 / row N: bounds entirely below the
        smallest key, above the largest, exact first/last key, full
        table, and empty filter ranges."""
        from repro.core import KeySchema
        from repro.kernels import table_slab_locate_many

        schema = KeySchema({"a": 10})
        vals = np.sort(rng.integers(100, 900, 500)).astype(np.int64)
        t = SortedTable.from_columns(
            {"a": vals}, {"m": np.ones(500)}, ("a",), schema
        ).place_on_device()
        qs = [
            Query(filters={"a": Range(0, 50)}),          # fully below
            Query(filters={"a": Range(950, 1024)}),       # fully above
            Query(filters={"a": Eq(int(vals[0]))}),       # first key
            Query(filters={"a": Eq(int(vals[-1]))}),      # last key
            Query(filters={}),                            # full table
            Query(filters={"a": Range(7, 7)}),            # empty range
        ]
        got = table_slab_locate_many(t, qs)
        np.testing.assert_array_equal(got, self._oracle(t, qs))
        assert tuple(got[0]) == (0, 0)
        assert tuple(got[4]) == (0, 500)
        assert tuple(got[5]) == (0, 0)

    def test_kernel_matches_ref_on_raw_lanes(self, rng):
        """Kernel vs jnp oracle on synthetic sorted lane arrays (wide
        two-lane column + narrow column), multiple row blocks."""
        from repro.kernels import slab_locate_batched, slab_locate_batched_ref

        n, q = 5000, 9
        packed = np.sort(rng.integers(0, 2**40, n)).astype(np.int64)
        narrow = rng.integers(0, 50, n).astype(np.int64)  # not part of order
        keys = np.stack(_lane_split(packed, 2) + _lane_split(narrow, 1))
        b_lo = rng.integers(0, 2**40, (q,)).astype(np.int64)
        b_hi = b_lo + rng.integers(0, 2**39, (q,))
        slab_lo = np.stack(_lane_split(b_lo, 2) + [np.zeros(q, np.int32)], axis=1)
        slab_hi = np.stack(_lane_split(b_hi, 2) + [np.full(q, 49, np.int32)], axis=1)
        limits = np.tile(np.array([[0, n]], np.int64), (q, 1))
        got = np.asarray(
            slab_locate_batched(keys, slab_lo, slab_hi, limits, block_n=512)
        )
        want = np.asarray(
            slab_locate_batched_ref(
                jnp.asarray(keys), jnp.asarray(slab_lo), jnp.asarray(slab_hi),
                jnp.asarray(limits, jnp.int32),
            )
        )
        np.testing.assert_array_equal(got, want)

    def test_requires_single_sorted_run(self, rng):
        from repro.kernels import table_slab_locate_many

        kc = {"a": rng.integers(0, 16, 300)}
        t = SortedTable.from_columns(kc, {"m": np.ones(300)}, ("a",)).place_on_device()
        merged = t.merge_insert({"a": np.array([3])}, {"m": np.array([1.0])})
        with pytest.raises(ValueError, match="single sorted run"):
            table_slab_locate_many(merged, [Query(filters={})])
        host = SortedTable.from_columns(kc, {"m": np.ones(300)}, ("a",))
        with pytest.raises(ValueError, match="device-resident"):
            table_slab_locate_many(host, [Query(filters={})])


class TestFusedLocateScan:
    """scan_agg_locate_batched (fused kernel) vs oracles and the engine."""

    def test_kernel_matches_ref(self, rng):
        from repro.kernels import scan_agg_locate_batched, scan_agg_locate_batched_ref

        n, q, k = 4000, 11, 3
        keys = np.sort(rng.integers(0, 64, (k, n)), axis=1).astype(np.int32)
        vals = rng.uniform(-2, 2, (3, n)).astype(np.float32)
        res_lo = rng.integers(0, 32, (q, k)).astype(np.int32)
        res_hi = (res_lo + rng.integers(0, 32, (q, k))).astype(np.int32)
        slab_lo = rng.integers(0, 32, (q, k)).astype(np.int32)
        slab_hi = (slab_lo + rng.integers(0, 32, (q, k))).astype(np.int32)
        limits = np.tile(np.array([[0, n]], np.int32), (q, 1))
        limits[2] = (0, 0)  # one dead query
        sel = rng.integers(0, 3, q).astype(np.int32)
        got = scan_agg_locate_batched(
            keys, vals, res_lo, res_hi, slab_lo, slab_hi, limits, sel, block_n=512
        )
        want = scan_agg_locate_batched_ref(
            jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(res_lo),
            jnp.asarray(res_hi), jnp.asarray(slab_lo), jnp.asarray(slab_hi),
            jnp.asarray(limits), jnp.asarray(sel),
        )
        assert np.asarray(got[1]).dtype == np.int32  # exact int counts
        assert np.asarray(got[2]).dtype == np.int32
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-5, atol=1e-3)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))

    def test_block_size_and_chunking_invariance(self, rng):
        from repro.kernels import scan_agg_locate_batched
        from repro.kernels.slab_locate import scan_agg_locate_batched as raw

        n, q = 3000, 21
        keys = np.sort(rng.integers(0, 16, (2, n)), axis=1).astype(np.int32)
        vals = rng.uniform(0, 1, n).astype(np.float32)
        res_lo = rng.integers(0, 8, (q, 2)).astype(np.int32)
        res_hi = (res_lo + rng.integers(1, 8, (q, 2))).astype(np.int32)
        limits = np.tile(np.array([[0, n]], np.int32), (q, 1))
        a = raw(keys, vals, res_lo, res_hi, res_lo, res_hi, limits, block_n=128)
        b = raw(keys, vals, res_lo, res_hi, res_lo, res_hi, limits, block_n=1024)
        c = raw(keys, vals, res_lo, res_hi, res_lo, res_hi, limits, block_n=128, max_q=8)
        for x, y in ((a, b), (a, c)):
            np.testing.assert_allclose(np.asarray(x[0]), np.asarray(y[0]), rtol=1e-6)
            np.testing.assert_array_equal(np.asarray(x[1]), np.asarray(y[1]))
            np.testing.assert_array_equal(np.asarray(x[2]), np.asarray(y[2]))

    @pytest.mark.parametrize("use_pallas", [True, False])
    def test_table_execute_matches_numpy_engine(self, rng, use_pallas):
        from repro.kernels import table_execute_device_many

        kc = {"a": rng.integers(0, 30, 4000), "b": rng.integers(0, 30, 4000)}
        vc = {"m": rng.uniform(0, 5, 4000), "w": rng.uniform(-2, 2, 4000)}
        dev = SortedTable.from_columns(kc, vc, ("b", "a")).place_on_device()
        host = SortedTable.from_columns(kc, vc, ("b", "a"))
        qs = [
            Query(filters={"a": Range(3, 20), "b": Eq(7)}, agg="sum", value_col="m"),
            Query(filters={"b": Range(2, 9)}, agg="count"),
            Query(filters={"a": Eq(5)}, agg="select"),
            Query(filters={"a": Range(4, 4)}, agg="count"),   # empty range
            Query(filters={"b": Range(4, 4)}, agg="select"),  # empty select
            Query(filters={}, agg="sum", value_col="w"),
        ]
        out = table_execute_device_many(dev, qs, use_pallas=use_pallas)
        for q, rd in zip(qs, out):
            rh = host.execute(q)
            assert rd.rows_scanned == rh.rows_scanned
            assert rd.rows_matched == rh.rows_matched
            np.testing.assert_allclose(rd.value, rh.value, rtol=1e-5)
            if q.agg == "select":
                np.testing.assert_array_equal(rd.selected, rh.selected)

    def test_agg_validation(self, rng):
        from repro.kernels import table_execute_device_many

        kc = {"a": rng.integers(0, 8, 100)}
        vc = {"m": rng.uniform(0, 1, 100)}
        t = SortedTable.from_columns(kc, vc, ("a",)).place_on_device()
        with pytest.raises(ValueError, match="sum/count/select"):
            table_execute_device_many(t, [Query(filters={}, agg="median")])
        with pytest.raises(ValueError, match="value_col"):
            table_execute_device_many(t, [Query(filters={}, agg="sum")])
        with pytest.raises(KeyError):
            table_execute_device_many(
                t, [Query(filters={}, agg="sum", value_col="nope")]
            )


class TestSelectCompact:
    def test_kernel_matches_ref_and_nonzero(self, rng):
        from repro.kernels import select_compact_batched, select_compact_batched_ref

        n, q = 7000, 6  # several 2048-row blocks exercise the carry
        keys = rng.integers(0, 10, (2, n)).astype(np.int32)
        res_lo = rng.integers(0, 5, (q, 2)).astype(np.int32)
        res_hi = (res_lo + rng.integers(1, 6, (q, 2))).astype(np.int32)
        limits = np.tile(np.array([[0, n]], np.int32), (q, 1))
        limits[3] = (100, 900)  # a restricted window
        mask = np.ones((q, n), bool)
        ridx = np.arange(n)
        for j in range(q):
            m = (ridx >= limits[j, 0]) & (ridx < limits[j, 1])
            for lane in range(2):
                m &= (keys[lane] >= res_lo[j, lane]) & (keys[lane] < res_hi[j, lane])
            mask[j] = m
        counts = mask.sum(axis=1)
        width = 128
        while width < counts.max():
            width *= 2
        got = np.asarray(
            select_compact_batched(
                keys, res_lo, res_hi, limits, out_width=width, block_n=512
            )
        )
        want = np.asarray(
            select_compact_batched_ref(
                jnp.asarray(keys), jnp.asarray(res_lo), jnp.asarray(res_hi),
                jnp.asarray(limits), out_width=width,
            )
        )
        np.testing.assert_array_equal(got, want)
        for j in range(q):
            np.testing.assert_array_equal(
                got[j, : counts[j]], np.nonzero(mask[j])[0]
            )

    def test_width_exactly_count(self, rng):
        """out_width == max matched count: the clamp path must not
        corrupt the last slot."""
        from repro.kernels import select_compact_batched

        n = 600
        keys = np.zeros((1, n), np.int32)
        keys[0, 5:133] = 1  # exactly 128 matches
        res_lo = np.array([[1]], np.int32)
        res_hi = np.array([[2]], np.int32)
        limits = np.array([[0, n]], np.int32)
        got = np.asarray(
            select_compact_batched(keys, res_lo, res_hi, limits, out_width=128, block_n=256)
        )
        np.testing.assert_array_equal(got[0], np.arange(5, 133))


class TestEcdfHist:
    @pytest.mark.parametrize("N,B,W", [(100, 8, 1), (4096, 64, 3), (10_000, 512, 2),
                                       (3000, 1024, 7), (555, 16, 16)])
    def test_shape_sweep(self, rng, N, B, W):
        col = rng.integers(0, B * W, N).astype(np.int32)
        got = np.asarray(ecdf_hist(col, n_bins=B, bin_width=W, block_n=256))
        want = np.asarray(ecdf_hist_ref(jnp.asarray(col), n_bins=B, bin_width=W))
        np.testing.assert_allclose(got, want)

    def test_total_mass(self, rng):
        col = rng.integers(0, 100, 5000).astype(np.int32)
        got = np.asarray(ecdf_hist(col, n_bins=100, bin_width=1))
        assert got.sum() == 5000

    def test_large_bins_fallback_to_ref(self, rng):
        """Past the kernel's 4096-bin budget the entry raises instead of
        silently answering from the reference; the reference itself
        still bins any width when asked for explicitly."""
        col = rng.integers(0, 10_000, 2000).astype(np.int32)
        with pytest.raises(ValueError, match="4096"):
            ecdf_hist(col, n_bins=5000, bin_width=2)
        got = np.asarray(ecdf_hist(col, n_bins=5000, bin_width=2, use_pallas=False))
        want = np.asarray(ecdf_hist_ref(jnp.asarray(col), n_bins=5000, bin_width=2))
        np.testing.assert_allclose(got, want)


class TestMergeRuns:
    """K-way merge-path kernel vs the lexsort oracle, the incremental
    row_map, and the rebuild escape hatch."""

    def _stacked(self, rng, n_base, runs, dom=16, layout=("a", "b")):
        kc = {"a": rng.integers(0, dom, n_base), "b": rng.integers(0, dom, n_base)}
        vc = {"m": rng.uniform(0, 1, n_base)}
        t = SortedTable.from_columns(kc, vc, layout).place_on_device()
        for m in runs:
            t = t.merge_insert(
                {"a": rng.integers(0, dom, m), "b": rng.integers(0, dom, m)},
                {"m": rng.uniform(0, 1, m)},
            )
        return t

    @pytest.mark.parametrize("runs", [(1,), (100,), (37, 208, 5), (64, 64, 64, 64)])
    def test_positions_match_oracle_and_row_map(self, rng, runs):
        from repro.kernels import merge_run_positions, merge_run_positions_ref

        t = self._stacked(rng, 900, runs, dom=8)  # small domain: many ties
        st = t._device
        n_lanes = sum(st["col_parts"])
        got = merge_run_positions(
            st["keys"], st["run_starts"], st["n_rows"], n_lanes=n_lanes, block_n=256
        )
        want = merge_run_positions_ref(
            st["keys"], st["run_starts"], st["n_rows"], n_lanes=n_lanes
        )
        np.testing.assert_array_equal(got, want)
        # the merge tie rule IS the host merge order, so the kernel's
        # permutation equals the incrementally maintained row_map
        np.testing.assert_array_equal(got, st["row_map"])

    def test_block_size_invariance(self, rng):
        from repro.kernels import merge_run_positions

        t = self._stacked(rng, 700, (90, 33))
        st = t._device
        n_lanes = sum(st["col_parts"])
        outs = [
            merge_run_positions(
                st["keys"], st["run_starts"], st["n_rows"], n_lanes=n_lanes,
                block_n=bn,
            )
            for bn in (128, 512, 4096)
        ]
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)

    def test_merge_device_runs_equals_rebuild(self, rng):
        """Compacted state == place_on_device(rebuild=True) state, array
        for array — device order becomes host order with no re-upload."""
        import copy

        from repro.kernels import merge_device_runs

        t = self._stacked(rng, 1500, (200, 80, 41))
        compacted = merge_device_runs(t._device)
        rebuilt = copy.deepcopy(t).place_on_device(rebuild=True)._device
        assert compacted["n_runs"] == 1 and compacted["row_map"] is None
        assert compacted["run_starts"] == (0,)
        np.testing.assert_array_equal(
            np.asarray(compacted["keys"]), np.asarray(rebuilt["keys"])
        )
        np.testing.assert_array_equal(
            np.asarray(compacted["values_tile"]), np.asarray(rebuilt["values_tile"])
        )

    def test_wide_two_lane_columns(self, rng):
        """A 40-bit key column (two int32 lanes, lexicographic) merges
        correctly through the kernel."""
        from repro.core import KeySchema
        from repro.kernels import merge_device_runs

        schema = KeySchema({"a": 40, "b": 6})
        kc = {"a": rng.integers(0, 2**40, 1200).astype(np.int64),
              "b": rng.integers(0, 64, 1200).astype(np.int64)}
        vc = {"m": rng.uniform(0, 1, 1200)}
        t = SortedTable.from_columns(kc, vc, ("a", "b"), schema).place_on_device()
        t = t.merge_insert(
            {"a": rng.integers(0, 2**40, 150).astype(np.int64),
             "b": rng.integers(0, 64, 150).astype(np.int64)},
            {"m": rng.uniform(0, 1, 150)},
        )
        st = merge_device_runs(t._device)
        import copy

        rebuilt = copy.deepcopy(t).place_on_device(rebuild=True)._device
        np.testing.assert_array_equal(
            np.asarray(st["keys"]), np.asarray(rebuilt["keys"])
        )

    def test_compact_runs_preserves_results(self, rng):
        t = self._stacked(rng, 1000, (120, 60))
        qs = [Query(filters={"a": Eq(3)}, agg="count"),
              Query(filters={"b": Range(2, 9)}, agg="sum", value_col="m"),
              Query(filters={"a": Eq(5)}, agg="select")]
        before = [t.execute(q) for q in qs]
        t.compact_runs()
        assert t._device["n_runs"] == 1
        after = [t.execute(q) for q in qs]
        for b, a in zip(before, after):
            assert b.rows_matched == a.rows_matched
            assert b.rows_scanned == a.rows_scanned
            np.testing.assert_allclose(a.value, b.value, rtol=1e-5)
            if b.selected is not None:
                np.testing.assert_array_equal(a.selected, b.selected)

    def test_single_run_noop(self, rng):
        from repro.kernels import merge_device_runs, merge_run_positions

        t = self._stacked(rng, 500, ())
        st = t._device
        assert merge_device_runs(st)["n_runs"] == 1
        np.testing.assert_array_equal(
            merge_run_positions(st["keys"], st["run_starts"], 500, n_lanes=2),
            np.arange(500),
        )


class TestEcdfDeviceStats:
    """Satellite: ecdf_hist wired into TableStats.merge_rows — the
    device refresh must equal the host bincount path exactly."""

    def test_merge_rows_device_equals_host(self, rng):
        import copy

        from repro.core import KeySchema
        from repro.core.ecdf import TableStats

        schema = KeySchema({"a": 6, "b": 14})
        kc = {"a": rng.integers(0, 64, 4000), "b": rng.integers(0, 1 << 14, 4000)}
        host_stats = TableStats.from_columns(kc, schema)
        dev_stats = copy.deepcopy(host_stats)
        batch = {"a": rng.integers(0, 64, 900), "b": rng.integers(0, 1 << 14, 900)}
        host_stats.merge_rows(batch, device=False)
        dev_stats.merge_rows(batch, device=True)
        assert dev_stats.n_rows == host_stats.n_rows
        for c in ("a", "b"):
            np.testing.assert_array_equal(
                dev_stats.columns[c].counts, host_stats.columns[c].counts
            )
            assert dev_stats.columns[c].total == host_stats.columns[c].total

    def test_wide_domain_falls_back_to_host(self, rng):
        import copy

        from repro.core import KeySchema
        from repro.core.ecdf import TableStats

        schema = KeySchema({"w": 40})  # domain exceeds the int32 lanes
        kc = {"w": rng.integers(0, 2**40, 2000).astype(np.int64)}
        a = TableStats.from_columns(kc, schema)
        b = copy.deepcopy(a)
        batch = {"w": rng.integers(0, 2**40, 500).astype(np.int64)}
        a.merge_rows(batch, device=False)
        b.merge_rows(batch, device=True)  # silently host-path
        np.testing.assert_array_equal(a.columns["w"].counts, b.columns["w"].counts)

    def test_selectivities_identical_after_device_refresh(self, rng):
        import copy

        from repro.core import KeySchema
        from repro.core.ecdf import TableStats

        schema = KeySchema({"a": 10})
        kc = {"a": rng.integers(0, 1024, 3000)}
        a = TableStats.from_columns(kc, schema)
        b = copy.deepcopy(a)
        batch = {"a": rng.integers(0, 1024, 700)}
        a.merge_rows(batch, device=False)
        b.merge_rows(batch, device=True)
        xs = rng.uniform(0, 1024, 50)
        np.testing.assert_array_equal(
            a.columns["a"].cdf_many(xs), b.columns["a"].cdf_many(xs)
        )
