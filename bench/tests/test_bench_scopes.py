"""Device time by program scope: each op of a trace is mapped to its
instruction's op_name through the compiled HLO text of the program
whose module event encloses it. The programs here are the phi3 smoke
configuration's round programs, compiled on the CPU; the trace is made
from their instruction names."""
import pytest

import bench_tiny  # noqa: F401  (paths)
from harness import cell as cells
from harness import trace as T
from harness.clock import Spans
from harness.peaks import chip_peaks

LOCAL, FEDAVG = "jit_local_train", "jit_fedavg"
US = 1e3


@pytest.fixture(scope="module")
def hooks():
    from repro import configs
    from repro.fl.training import MeshTrainerHooks
    cfg = configs.get_config("phi3-mini-3.8b", smoke=True)
    return MeshTrainerHooks(["c0"], cfg=cfg, local_steps=2, batch=2,
                            seq=16, quantize=True)


@pytest.fixture(scope="module")
def programs(hooks):
    import run as bench_run
    return bench_run.program_op_names(hooks, 2, 16)


def _runs(programs, extra=()):
    """Two rounds, local then FedAvg, each run one op per instruction
    of its program (those with no op_name, such as parameters and
    relayouts, too) laid end to end; `extra` adds (module, name, ns) ops
    at the end of each run of that module. The local runs hold a `while`
    over all their ops."""
    t, ops, modules = 0.0, [], []
    for _ in range(2):
        for prog in (LOCAL, FEDAVG):
            start = t
            names = sorted(programs[prog])
            body = [[n, "fusion", "", 0.0, (1 + i % 7) * US]
                    for i, n in enumerate(names)]
            body += [[n, "fusion", "", 0.0, d] for m, n, d in extra
                     if m == prog]
            for op in body:
                op[3] = t
                t += op[4]
            if prog == LOCAL:
                ops.append(["while.1", "while", "", start, t - start])
            ops.extend(body)
            modules.append([f"{prog}(1234)", start, t - start])
            t += 5 * US                       # idle between programs
    return {"devices": [{"id": 0, "ops": ops, "modules": modules}],
            "host": [["bench.window", 0.0, t]]}


def _record(programs, trace):
    import run as bench_run
    lo, hi = T.window(trace)
    win = {"rounds": 2, "t0": lo / 1e9, "t_close": hi / 1e9}
    return bench_run.Record(cells.load_cell("phi3.1chip.int8"), trace, win,
                            Spans(), chip_peaks("TPU v5 lite"), programs)


def test_op_name_paths():
    body = "jit(local_train)/vmap()/while/body/closed_call"
    fwd = f"{body}/jvp(forward)/dot_general"
    bwd = f"{body}/transpose(jvp(forward))/while/body/dot_general"
    assert T.in_scope(fwd, "forward", "forward")
    assert not T.in_scope(fwd, "forward", "backward")
    assert T.in_scope(bwd, "forward", "backward")
    assert not T.in_scope(bwd, "forward", "forward")
    assert T.in_scope(bwd, "forward") and T.in_scope(fwd, "forward")
    assert T.in_scope(f"{body}/optimizer/add", "optimizer")
    assert not T.in_scope(f"{body}/optimizer/add", "optimizer", "forward")
    assert not T.in_scope("jit(fedavg)/sum/add", "forward")
    assert not T.in_scope("jit(forwarded)/add", "forward")
    assert not T.in_scope("jit(local_train)/vmap(forward)/add", "forward")


def test_op_names_of_hlo_text():
    text = ('HloModule jit_f, entry_computation_layout={()->f32[]}\n\n'
            'ENTRY %main.3 () -> f32[] {\n'
            '  %constant.1 = f32[] constant(1), metadata={op_name='
            '"jit(f)/x[\\\'a\\\']/add" source_line=3}\n'
            '  ROOT %add.2 = f32[] add(%constant.1, %constant.1), '
            'metadata={op_name="jit(f)/transpose(jvp(g))/add"}\n'
            '  %copy.3 = f32[] copy(%add.2)\n}\n')
    module, names = T.op_names(text)
    assert module == "jit_f"
    assert names == {"constant.1": "jit(f)/x[\\'a\\']/add",
                     "add.2": "jit(f)/transpose(jvp(g))/add",
                     "copy.3": ""}


def test_scopes_add_up_to_the_program(programs):
    assert set(programs) == {LOCAL, FEDAVG}
    r = _record(programs, _runs(programs))
    fwd = r.scope_ms(LOCAL, "forward", "forward")
    bwd = r.scope_ms(LOCAL, "forward", "backward")
    opt = r.scope_ms(LOCAL, "optimizer")
    assert fwd > 0 and bwd > 0 and opt > 0
    local = programs[LOCAL]
    other = sum((1 + i % 7) * US / 1e6 for i, n in enumerate(sorted(local))
                if not any(T.in_scope(local[n], s)
                           for s in ("forward", "optimizer")))
    assert other > 0
    total = r.scope_ms(LOCAL)
    assert fwd + bwd + opt + other == pytest.approx(total, rel=1e-12)
    # the ops fill each run: the `while` around them covers nothing
    assert total == pytest.approx(
        cells.metric_reader("local_device_ms")(r), rel=1e-12)
    assert r.scope_ms(FEDAVG) == pytest.approx(
        cells.metric_reader("fedavg_device_ms")(r), rel=1e-12)
    for name, want in (("local_fwd_ms", fwd), ("local_bwd_ms", bwd),
                       ("local_opt_ms", opt)):
        assert cells.metric_reader(name)(r) == want


def test_a_repeated_name_lands_in_its_own_program(programs):
    local, fedavg = programs[LOCAL], programs[FEDAVG]
    name = next(n for n in sorted(set(local) & set(fedavg))
                if T.in_scope(local[n], "forward", "backward")
                and T.in_scope(fedavg[n], "sum"))
    base = _record(programs, _runs(programs))
    more = _record(programs, _runs(programs, [(FEDAVG, name, 1e6)]))
    for scope, pass_ in (("forward", "forward"), ("forward", "backward"),
                         ("optimizer", None)):
        assert more.scope_ms(LOCAL, scope, pass_) == \
            base.scope_ms(LOCAL, scope, pass_)
    assert more.scope_ms(FEDAVG, "sum") == pytest.approx(
        base.scope_ms(FEDAVG, "sum") + 1.0)


def test_no_program_text_reads_nothing(programs):
    """Without the programs' text, or outside their runs, the scope
    metrics give nothing, never 0."""
    trace = _runs(programs)
    assert _record({}, trace).scope_ms(LOCAL, "optimizer") is None
    r = _record(programs, trace)
    r.devices = [{"id": 0, "ops": [], "modules": []}]
    for name in ("local_fwd_ms", "local_bwd_ms", "local_opt_ms"):
        assert cells.metric_reader(name)(r) is None


def test_an_op_not_in_the_text_reads_nothing(programs):
    """An op inside a program's runs that its text does not name: the
    text is of another executable, and no scope is read from it."""
    r = _record(programs, _runs(programs, [(LOCAL, "fusion.987654", 1e6)]))
    assert r.unknown_ops(LOCAL) == 2 and r.unknown_ops(FEDAVG) == 0
    for name in ("local_fwd_ms", "local_bwd_ms", "local_opt_ms"):
        assert cells.metric_reader(name)(r) is None
    assert r.scope_ms(FEDAVG, "sum") is not None


def test_text_of_another_executable_reads_nothing(hooks, programs):
    """Lowered as the window ran them, the programs are found in memory
    and read; lowered with another row dtype they compile anew, and the
    harness keeps no text, so the scope metrics read None."""
    import run as bench_run
    assert bench_run.window_op_names(hooks, 2, 16) == programs
    other = bench_run.window_op_names(hooks, 2, 16, rows_dtype="int16")
    assert other == {}
    r = _record(other, _runs(programs))
    for name in ("local_fwd_ms", "local_bwd_ms", "local_opt_ms"):
        assert cells.metric_reader(name)(r) is None
