import numpy as np

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
