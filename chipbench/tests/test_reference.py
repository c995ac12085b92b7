import numpy as np
import pytest

from harness import reference, spec, tpch, traffic

ROWS = 40_000


@pytest.fixture(scope="module")
def data():
    kc, vc = tpch.generate_orders(ROWS, 99)
    dom = tpch.domains(ROWS)
    return kc, vc, dom, reference.Reference(kc, vc, dom)


def brute(kc, vc, mix, i):
    keep = np.ones(ROWS, bool)
    for c in mix.templates[mix.tmpl[i]]["filters"]:
        keep &= (kc[c] >= mix.lo[c][i]) & (kc[c] < mix.hi[c][i])
    return np.nonzero(keep)[0]


@pytest.mark.parametrize("name", ["q1q2-hr-r80", "q1q2sel-hr-r80"])
def test_reference_equals_a_full_scan(data, name):
    kc, vc, dom, ref = data
    templates = spec.load_json(spec.traffic_file(name))["templates"]
    mix = traffic.queries(templates, dom, 400, [1, 1])
    got = ref.answers(mix, np.arange(len(mix)))
    assert sum(a.matched for a in got) > 20
    for i, a in enumerate(got):
        rows = brute(kc, vc, mix, i)
        assert a.matched == rows.size
        if mix.agg(i) == "sum":
            assert a.value == pytest.approx(float(np.sum(vc["totalprice"][rows])), rel=1e-12, abs=1e-9)
        else:
            assert np.array_equal(a.rows, ref.row_contents(rows))


def test_full_scan_for_a_template_without_equality(data):
    kc, vc, dom, ref = data
    t = [{"name": "R", "agg": "count", "filters": {"orderdate": {"span": [1, 3]}}}]
    mix = traffic.queries(t, dom, 5, [2, 1])
    for i, a in enumerate(ref.answers(mix, np.arange(5))):
        assert a.matched == brute(kc, vc, mix, i).size == a.value


def test_control_is_one_precision_step_down(data):
    kc, vc, dom, ref = data
    templates = spec.load_json(spec.traffic_file("q1q2-hr-r80"))["templates"]
    mix = traffic.queries(templates, dom, 400, [1, 1])
    idx = np.arange(len(mix))
    exact, low = ref.answers(mix, idx), ref.answers(mix, idx, control=True)
    c = reference.compare(low, exact, [mix.agg(i) for i in idx])
    assert c.count_mismatches == 0
    assert 1e-4 < c.sum_rel_gap < 1e-2  # bfloat16 keeps 8 bits of mantissa
    assert reference.compare(exact, exact, [mix.agg(i) for i in idx]).sum_rel_gap == 0


def test_compare_counts_each_kind_of_fault():
    w = [reference.Answer(10.0, 1), reference.Answer(2.0, 2, np.ones((2, 5))),
         reference.Answer(3.0, 3)]
    aggs = ["sum", "select", "count"]
    ok = reference.compare(w, w, aggs)
    assert (ok.failed, ok.count_mismatches, ok.row_mismatches, ok.sum_rel_gap) == (0, 0, 0, 0.0)
    bad = [reference.Answer(10.1, 1), reference.Answer(2.0, 2, np.zeros((2, 5))), None]
    c = reference.compare(bad, w, aggs)
    assert (c.failed, c.row_mismatches) == (1, 1)
    assert not c.correct({"sum_rel_gap": 1.0})
    assert c.sum_rel_gap == pytest.approx(0.01)
    nan = reference.compare([reference.Answer(float("nan"), 1)], w[:1], aggs[:1])
    assert nan.sum_rel_gap == float("inf")
    refused = reference.compare([None], w[:1], aggs[:1])
    assert refused.failed == 1 and refused.compared == 0
    assert not refused.correct({"sum_rel_gap": 1.0})


def test_a_read_sees_only_the_rows_below_its_visible_count(data):
    kc, vc, dom, ref = data
    templates = spec.load_json(spec.traffic_file("q1q2-hr-r80"))["templates"]
    mix = traffic.queries(templates, dom, 200, [4, 1])
    idx = np.arange(len(mix))
    visible = np.random.default_rng(3).integers(0, ROWS + 1, idx.size)
    got = ref.answers(mix, idx, visible=visible)
    for i, a in enumerate(got):
        rows = brute(kc, vc, mix, i)
        rows = rows[rows < visible[i]]
        assert a.matched == rows.size
        assert a.value == pytest.approx(float(np.sum(vc["totalprice"][rows])), rel=1e-12, abs=1e-9)
    everything = ref.answers(mix, idx, visible=np.full(idx.size, ROWS))
    assert [a.value for a in everything] == [a.value for a in ref.answers(mix, idx)]


def test_fingerprint_ignores_row_order_and_sees_any_changed_row(data):
    kc, vc, _dom, _ref = data
    n, digest = reference.fingerprint(kc, vc)
    order = np.random.default_rng(1).permutation(ROWS)
    assert reference.fingerprint({c: v[order] for c, v in kc.items()},
                                 {c: v[order] for c, v in vc.items()}) == (n, digest)
    assert n == ROWS
    cents = dict(vc, totalprice=vc["totalprice"] + np.where(np.arange(ROWS) == 5, 0.01, 0.0))
    assert reference.fingerprint(kc, cents)[1] != digest
    fewer = reference.fingerprint({c: v[1:] for c, v in kc.items()}, {c: v[1:] for c, v in vc.items()})
    assert fewer[0] == ROWS - 1 and fewer[1] != digest


def test_device_fingerprint_reads_values_as_float32_and_the_count_lane(data):
    kc, vc, _dom, _ref = data
    n, digest = reference.device_fingerprint(kc, vc)
    f32 = {c: np.asarray(v, np.float32) for c, v in vc.items()}
    assert reference.device_fingerprint(kc, f32, np.ones(ROWS, np.float32)) == (n, digest)
    order = np.random.default_rng(2).permutation(ROWS)
    assert reference.device_fingerprint({c: v[order] for c, v in kc.items()},
                                        {c: v[order] for c, v in f32.items()}) == (n, digest)
    ones = np.ones(ROWS, np.float32)
    ones[7] = 0.0
    assert reference.device_fingerprint(kc, f32, ones)[1] != digest
    bumped = dict(f32, totalprice=np.nextafter(f32["totalprice"], np.float32(np.inf)))
    assert reference.device_fingerprint(kc, bumped)[1] != digest
