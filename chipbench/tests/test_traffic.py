import numpy as np
import pytest

from harness import spec, tpch, traffic

DOM = tpch.domains(7_500_000)
Q1Q2 = spec.load_json(spec.traffic_file("q1q2-hr-r80"))["templates"]


def test_same_seed_same_queries_and_arrivals():
    a = traffic.queries(Q1Q2, DOM, 500, [2**31 + 7, 1])
    b = traffic.queries(Q1Q2, DOM, 500, [2**31 + 7, 1])
    for c in a.lo:
        assert np.array_equal(a.lo[c], b.lo[c]) and np.array_equal(a.hi[c], b.hi[c])
    assert np.array_equal(traffic.arrivals(100.0, 10.0, [5, 2]), traffic.arrivals(100.0, 10.0, [5, 2]))


def test_templates_alternate_and_spans_cycle_through_every_size():
    mix = traffic.queries(Q1Q2, DOM, 600, [11, 1])
    assert list(mix.tmpl[:4]) == [0, 1, 0, 1]
    q2 = np.nonzero(mix.tmpl == 1)[0]
    spans = mix.hi["orderdate"][q2] - mix.lo["orderdate"][q2]
    # 300 Q2 = ten cycles of the sizes 1..30, for every seed
    assert sorted(spans) == sorted(list(range(1, 31)) * 10)
    other = traffic.queries(Q1Q2, DOM, 600, [12, 1])
    o_spans = other.hi["orderdate"][q2] - other.lo["orderdate"][q2]
    assert sorted(o_spans) == sorted(spans) and not np.array_equal(o_spans, spans)
    q1 = np.nonzero(mix.tmpl == 0)[0]
    assert np.all(mix.hi["orderdate"][q1] - mix.lo["orderdate"][q1] == 1)
    assert np.all(mix.lo["custkey"][q1] == 0) and np.all(mix.hi["custkey"][q1] == DOM["custkey"])
    assert np.all(mix.hi["orderdate"][q2] <= DOM["orderdate"])


def test_arrivals_have_a_fixed_count_and_gap_set():
    a = traffic.arrivals(250.0, 4.0, [1, 2])
    b = traffic.arrivals(250.0, 4.0, [2, 2])
    assert len(a) == len(b) == 1000
    assert np.all(np.diff(a) > 0) and 0 < a[0] and a[-1] < 4.0
    ga, gb = np.diff(np.concatenate([[0], a])), np.diff(np.concatenate([[0], b]))
    assert not np.allclose(ga, gb)
    # the same gaps in another order: their sorted values agree but for
    # the one gap past the window each seed drops
    assert np.mean(np.isin(np.round(ga, 12), np.round(gb, 12))) > 0.99
    assert abs(np.mean(ga) - 1 / 250) < 1e-4


def test_program_queries_carry_the_filters():
    mix = traffic.queries(Q1Q2, DOM, 4, [3, 1])
    qs = traffic.to_queries(mix)
    for i, q in enumerate(qs):
        assert q.agg == "sum" and q.value_col == "totalprice"
        for c, f in mix.templates[mix.tmpl[i]]["filters"].items():
            assert q.filter_bounds(None, c) == (mix.lo[c][i], mix.hi[c][i])
            assert q.is_equality_on(c) == bool(f.get("eq"))


def test_new_orders_hold_keys_no_loaded_order_holds():
    loaded = 40_000
    kc, vc = tpch.new_orders(loaded, 3000, [7, 5, 1])
    again, _ = tpch.new_orders(loaded, 3000, [7, 5, 1])
    assert all(np.array_equal(kc[c], again[c]) for c in kc)
    assert np.intersect1d(vc["orderkey"], tpch.order_keys(loaded)).size == 0
    assert np.unique(vc["orderkey"]).size == 3000
    dom = tpch.domains(loaded)
    assert all(0 <= kc[c].min() and kc[c].max() < dom[c] for c in dom)
    base_k, base_v = tpch.generate_orders(loaded, 7)
    assert set(kc) == set(base_k) and set(vc) == set(base_v)


def test_each_refresh_is_due_whole_at_its_time():
    due, ends = traffic.write_stream({"refresh_at_s": [5.0, 0.0]}, tpch.rf1_rows(7_500_000))
    assert tpch.rf1_rows(7_500_000) == 7500 and ends.tolist() == [7500, 15000]
    assert due[:7500].tolist() == [0.0] * 7500 and due[7500:].tolist() == [5.0] * 7500
    with pytest.raises(ValueError):
        traffic.write_stream({"refresh_at_s": []}, 10)


def test_aimed_queries_cover_their_row_and_keep_their_work():
    templates = spec.load_json(spec.traffic_file("q1q2-newsales-closed64"))["templates"]
    dom = tpch.domains(40_000)
    mix = traffic.queries(templates, dom, 400, [3, 1])
    plain = traffic.queries(templates, dom, 400, [3, 1])
    kc, _vc = tpch.new_orders(40_000, 40, [3, 5, 1])
    at = traffic.aim_at_rows(mix, dom, kc, 8, [3, 5, 2])
    assert at.tolist() == list(range(7, 400, 8))
    untouched = np.setdiff1d(np.arange(400), at)
    for c in mix.lo:
        assert np.array_equal(mix.lo[c][untouched], plain.lo[c][untouched])
        # the same span sizes, inside the domain
        assert np.array_equal(mix.hi[c] - mix.lo[c], plain.hi[c] - plain.lo[c])
        assert mix.lo[c].min() >= 0 and mix.hi[c].max() <= dom[c]
    rows = np.stack([kc[c] for c in sorted(mix.lo)], axis=1)
    for i in at:
        lo = np.array([mix.lo[c][i] for c in sorted(mix.lo)])
        hi = np.array([mix.hi[c][i] for c in sorted(mix.lo)])
        assert np.any(np.all((rows >= lo) & (rows < hi), axis=1))
