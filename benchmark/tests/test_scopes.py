"""Operation -> scope, on the two traces recorded on a v5e that are
checked in beside this file, and the wire reader against the generated
protobuf classes where those import."""

from pathlib import Path

import pytest

from benchmark import scopes, trace

PLAIN = Path(__file__).with_name("fixture_trace.xplane.pb")
SCOPED = Path(__file__).with_name("fixture_scopes.xplane.pb")


def test_scope_of():
    assert scopes.scope_of(
        "jit(_train_forest_impl)/while/body/h2o.tree.route/gather:") == \
        "h2o.tree.route"
    # the deepest h2o. component wins
    assert scopes.scope_of(
        "jit(f)/h2o.tree.hist.contract/shard_map/h2o.coll.hist.table/"
        "psum:") == "h2o.coll.hist.table"
    assert scopes.scope_of("jit(<lambda>)/dot_general:") == scopes.UNSCOPED
    assert scopes.scope_of("") == scopes.UNSCOPED
    assert scopes.scope_of(None) == scopes.UNSCOPED
    # a host span's name is not a device scope
    assert scopes.scope_of("jit(f)/h2o:train.bin/add:") == scopes.UNSCOPED


def test_by_scope_sums_and_counts_unscoped():
    ops = {"%a": (2.0, 3), "%b": (1.0, 1), "%c": (0.5, 1), "%d": (0.5, 9)}
    paths = {"%a": "jit(f)/h2o.tree.route/gather:",
             "%b": "jit(f)/h2o.tree.route/select_n:",
             "%c": "jit(f)/while/body/add:"}          # %d: no tf_op at all
    got = scopes.by_scope(ops, paths)
    assert got == {"h2o.tree.route": 3.0, scopes.UNSCOPED: 1.0}
    assert sum(got.values()) == sum(s for s, _ in ops.values())


@pytest.mark.skipif(not PLAIN.is_file(), reason="no recorded trace")
def test_plain_fixture_maps_the_matmul_to_its_jit():
    paths = scopes.op_paths(PLAIN)
    conv = [v for k, v in paths.items() if k.startswith("%convolution")]
    assert conv == ["jit(<lambda>)/dot_general:"]
    tr = trace.reduce_xplane(PLAIN)
    # the names are the full HLO strings that key the reduction's ops
    (name,) = [k for k in paths if k.startswith("%convolution")]
    assert tr["ops"][name][0] > 0 and tr["ops"][name][1] == 12
    # no h2o. scope anywhere: all of it is unscoped
    assert set(scopes.by_scope(tr["ops"], paths)) == {scopes.UNSCOPED}


@pytest.mark.parametrize("fixture", [PLAIN, SCOPED],
                         ids=["plain", "scoped"])
def test_wire_reader_equals_generated_classes(fixture):
    if not fixture.is_file():
        pytest.skip("no recorded trace")
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    space.ParseFromString(fixture.read_bytes())
    want = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for meta in plane.event_metadata.values():
            for st in meta.stats:
                if names.get(st.metadata_id) != "tf_op":
                    continue
                kind = st.WhichOneof("value")
                op = st.str_value if kind == "str_value" else \
                    names.get(st.ref_value, "")
                if op:
                    want.setdefault(meta.name, op)
    assert want and scopes.op_paths(fixture) == want


@pytest.mark.skipif(not SCOPED.is_file(), reason="no recorded trace")
def test_scoped_fixture():
    tr = trace.reduce_xplane(SCOPED)
    paths = scopes.op_paths(SCOPED)
    got = scopes.by_scope(tr["ops"], paths)
    total = sum(got.values())
    # two named scopes and what the program left unnamed (the scan's
    # plumbing, the cos and its sum), each with time of its own
    assert {"h2o.fixture.matmul", "h2o.fixture.loop",
            scopes.UNSCOPED} <= set(got)
    assert all(v > 0 for v in got.values())
    # shares sum to 100
    assert sum(100 * v / total for v in got.values()) == \
        pytest.approx(100.0, abs=1e-9)
    # the while is not counted twice: self times add up to the busy time
    assert total == pytest.approx(tr["busy_s"], rel=0.01)
    whiles = [s for n, (s, _) in tr["ops"].items()
              if n.startswith("%while")]
    assert whiles and sum(whiles) < 0.2 * got["h2o.fixture.loop"]
    # an operation under no scope is counted as unscoped
    loose = [n for n in tr["ops"]
             if scopes.scope_of(paths.get(n)) == scopes.UNSCOPED]
    assert any("cos" in paths.get(n, "") or "reduce" in paths.get(n, "")
               for n in loose)
    # the idle gaps are the host's sleeps, named by the h2o: span
    gaps = scopes.gaps_by_span(SCOPED)
    assert gaps[0][0] == "h2o:fixture.sleep"
    assert sum(s for _, s in gaps) == pytest.approx(
        tr["window_s"] - tr["busy_s"], rel=0.05)


@pytest.mark.skipif(not SCOPED.is_file(), reason="no recorded trace")
def test_command_line_prints_the_table(capsys):
    assert scopes.main(["scopes", str(SCOPED)]) == 0
    out = capsys.readouterr().out
    assert "h2o.fixture.matmul" in out and "unscoped" in out
    assert "h2o:fixture.sleep" in out
