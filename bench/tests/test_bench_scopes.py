"""The training step's named scopes, from the program to the per-layer
metrics: every matmul and every gather and scatter of the step lies in a
scope, the scopes leave the compiled program as it was, the trace's
metadata gives each op its path, and device time is charged to the
innermost scope."""
import contextlib
import json
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, scopes, trace, xspace
from bench.tests import tiny

DATA = Path(__file__).parent / "data"
SMALL = DATA / "tpu_small.xplane.pb"
SCOPED = DATA / "tpu_scoped.xplane.pb"
DDP_SCOPES = scopes.STEP_SCOPES + ("topk", "scatter_add")
SEQ, ROWS = 32, 4

# one HLO instruction: its name, opcode and metadata
INSTR = re.compile(r"^\s*(?:ROOT )?%?(\S+) = .*? ([a-z][\w-]*)\(.*?"
                   r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
METADATA = re.compile(r",? metadata=\{(?:[^{}\"]|\"(?:[^\"\\]|\\.)*\")*\}")


def _shapes(cfg):
    from repro.models.transformer import init_params
    from repro.optim import make_optimizer
    opt_init, opt_update = make_optimizer("adam", weight_decay=0.01)
    p = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((ROWS, SEQ), jnp.int32),
             "labels": jax.ShapeDtypeStruct((ROWS, SEQ), jnp.int32),
             "sample_weights": jax.ShapeDtypeStruct((ROWS,), jnp.float32)}
    return p, jax.eval_shape(opt_init, p), batch, opt_update


def train_hlo() -> str:
    """The compiled HLO of the benchmark's train step on the tiny
    configuration, built as ``bench/drivers/train_step.py`` builds it."""
    from repro.launch.train import train_ctx
    from repro.optim import warmup_cosine
    from repro.train import make_train_step
    cfg = program.model_config(tiny.TINY_CONFIG)
    p, o, batch, opt_update = _shapes(cfg)
    fn = jax.jit(make_train_step(cfg, train_ctx(SEQ), opt_update,
                                 warmup_cosine(1e-3, 2, 10)),
                 donate_argnums=(0, 1))
    step = jax.ShapeDtypeStruct((), jnp.int32)
    return fn.lower(p, o, batch, step).compile().as_text()


def ddp_hlo() -> str:
    """The compiled HLO of the DDP compressed program on the tiny
    configuration, on a one-device mesh."""
    from jax.sharding import Mesh

    from repro.launch.train import train_ctx
    from repro.optim import warmup_cosine
    from repro.train.ddp import make_ddp_steps
    cfg = program.model_config(tiny.TINY_CONFIG)
    p, o, batch, opt_update = _shapes(cfg)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    _, comp, _, _ = make_ddp_steps(cfg, train_ctx(SEQ), mesh, opt_update,
                                   warmup_cosine(1e-3, 2, 10), 0.1, p)
    rates = jax.ShapeDtypeStruct((1,), jnp.float32)
    step = jax.ShapeDtypeStruct((), jnp.int32)
    return jax.jit(comp).lower(p, o, batch, rates, step).compile().as_text()


def stripped(hlo: str) -> str:
    """The program without its debug information: each instruction's
    metadata, and the tables of source locations that metadata points
    into."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif line.startswith(("%", "ENTRY")):
            skip = False
        if not skip:
            out.append(METADATA.sub("", line))
    return "\n".join(out)


def instructions(hlo: str):
    """(name, opcode, scope path) of each instruction with metadata."""
    return [m.groups() for m in map(INSTR.match, hlo.splitlines()) if m]


@pytest.fixture(scope="module")
def train_text():
    return train_hlo()


@pytest.mark.parametrize("which", ["train", "ddp"])
def test_matmuls_gathers_and_scatters_lie_in_scopes(which, train_text):
    hlo = train_text if which == "train" else ddp_hlo()
    names = scopes.STEP_SCOPES if which == "train" else DDP_SCOPES
    found = set()
    for name, opcode, path in instructions(hlo):
        scope = scopes.scope_of(path, names)
        found.add(scope)
        if opcode in ("dot", "convolution"):
            assert scope in ("attention", "mlp", "vocab"), (name, path)
        elif opcode in ("gather", "scatter"):
            assert scope in ("vocab", "topk", "scatter_add"), (name, path)
    assert set(names) <= found


def test_scopes_leave_the_compiled_step_unchanged(train_text, monkeypatch):
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = train_hlo()
    jax.clear_caches()
    assert "/attention/" not in plain and "/attention/" in train_text
    assert stripped(plain) == stripped(train_text)


def _tf_ops_by_protobuf(path: Path) -> dict:
    """The ``tf_op`` stats as TensorFlow's own ``XSpace`` message reads
    them, in a child process: TensorFlow stays out of this one."""
    code = (
        "import json, sys\n"
        "from tensorflow.tsl.profiler.protobuf import xplane_pb2\n"
        "xs = xplane_pb2.XSpace()\n"
        "xs.ParseFromString(open(sys.argv[1], 'rb').read())\n"
        "out = {}\n"
        "for p in xs.planes:\n"
        "    if not p.name.startswith('/device:TPU:'):\n"
        "        continue\n"
        "    ids = {k for k, m in p.stat_metadata.items()"
        " if m.name == 'tf_op'}\n"
        "    out[p.name] = {m.name: s.str_value"
        " for m in p.event_metadata.values() for s in m.stats"
        " if s.metadata_id in ids}\n"
        "print(json.dumps(out))\n")
    r = subprocess.run([sys.executable, "-c", code, str(path)],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_xspace_reads_the_tf_op_paths():
    got = xspace.event_stats(SMALL.read_bytes(), "tf_op", "/device:TPU:")
    paths = {trace.op_name(k): v for k, v in got["/device:TPU:0"].items()}
    assert paths["broadcast_add_fusion"] == "jit(add)/add:"
    assert paths["fusion"] == "jit(<lambda>)/dot_general:"
    assert got == _tf_ops_by_protobuf(SMALL)


def test_xspace_rejects_a_cut_file():
    data = SMALL.read_bytes()
    with pytest.raises((ValueError, IndexError)):
        xspace.event_stats(data[:len(data) // 2], "tf_op", "/device:TPU:")


def test_read_keeps_each_ops_path():
    tr = scopes.read(str(SMALL), ())
    assert tr["scopes"]["/device:TPU:0"]["fusion"] == \
        "jit(<lambda>)/dot_general"
    # copies carry no path, and stay out of the map
    assert "copy-start" not in tr["scopes"]["/device:TPU:0"]


@pytest.mark.parametrize("path,want", [
    ("jit(f)/attention/dot_general", "attention"),
    ("jit(f)/jvp(vocab)/reduce_sum", "vocab"),
    ("jit(f)/transpose(jvp(attention))/dot_general", "attention"),
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", "mlp"),
    ("jit(f)/optimizer/mlp/add", "mlp"),
    ("jit(f)/attention/while/body/dynamic_slice", "attention"),
    ("jit(f)/transpose(jvp())/while/body/dynamic_update_slice", "unscoped"),
    ("jit(f)/attentions/add", "unscoped"),
    ("jit(mlp_helper)/add", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of_takes_the_innermost_name(path, want):
    assert scopes.scope_of(path, scopes.STEP_SCOPES) == want


def _hand_trace():
    # chip a: a while loop (0..100) around an attention and an mlp op, a
    # vocab op from the backward, an unscoped op, and an op with no path;
    # chip b: one optimizer op.  The window leaves out the op at 300.
    return {"window": [0, 250], "host": [],
            "devices": {
                "/device:TPU:0": [[0, 100, "while.1"], [10, 30, "fusion.1"],
                                  [50, 20, "fusion.2"], [120, 10, "fusion.3"],
                                  [140, 5, "fusion.4"], [150, 8, "copy.1"],
                                  [300, 9, "fusion.1"]],
                "/device:TPU:1": [[0, 40, "fusion.9"]]},
            "scopes": {
                "/device:TPU:0": {
                    "while.1": "jit(s)/transpose(jvp())/while",
                    "fusion.1": "jit(s)/jvp()/while/body/closed_call/"
                                "attention/attention/dot_general",
                    "fusion.2": "jit(s)/transpose(jvp())/while/body/"
                                "closed_call/checkpoint/mlp/dot_general",
                    "fusion.3": "jit(s)/transpose(jvp(vocab))/"
                                "jit(_take)/scatter-add",
                    "fusion.4": "jit(s)/transpose(jvp())/add_any"},
                "/device:TPU:1": {"fusion.9": "jit(s)/optimizer/add"}}}


def test_scope_seconds_charges_self_time_to_the_innermost_scope():
    got = scopes.scope_seconds(_hand_trace(), scopes.STEP_SCOPES)
    # mean over the two chips
    assert got == {"attention": pytest.approx(15e-9),
                   "mlp": pytest.approx(10e-9),
                   "vocab": pytest.approx(5e-9),
                   "optimizer": pytest.approx(20e-9),
                   "unscoped": pytest.approx((50 + 5 + 8) / 2 * 1e-9)}
    ops = scopes.scope_op_seconds(_hand_trace(), scopes.STEP_SCOPES)
    assert ops["unscoped"] == {"while.1": pytest.approx(25e-9),
                               "fusion.4": pytest.approx(2.5e-9),
                               "copy.1": pytest.approx(4e-9)}
    # the scopes and the rest add up to the ops' self time
    assert sum(got.values()) == pytest.approx(
        sum(trace.op_seconds(_hand_trace()).values()))


def test_an_op_name_with_two_paths_is_an_error():
    tr = _hand_trace()
    tr["scopes"]["/device:TPU:0"]["fusion.2"] = ["jit(a)/mlp/dot_general",
                                                 "jit(b)/add"]
    with pytest.raises(RuntimeError, match="fusion.2"):
        scopes.scope_seconds(tr, scopes.STEP_SCOPES)


def test_step_scope_ms_gives_nothing_without_scopes():
    tr = _hand_trace()
    assert scopes.step_scope_ms(None, 2) is None
    assert scopes.step_scope_ms(tr, 0) is None
    assert scopes.step_scope_ms(dict(tr, scopes={}), 2) is None
    # a program that names no scope: every path, none of the names
    plain = {d: {op: "jit(s)/add" for op in ops}
             for d, ops in tr["scopes"].items()}
    assert scopes.step_scope_ms(dict(tr, scopes=plain), 2) is None


def test_step_scope_ms_adds_up_to_the_scoped_time():
    tr = _hand_trace()
    secs = scopes.scope_seconds(tr, scopes.STEP_SCOPES)
    assert scopes.step_scope_ms(tr, 2) == {
        k: pytest.approx(v / 2 * 1e3) for k, v in secs.items()}


@pytest.fixture(scope="module")
def scoped():
    raw = scopes.read(str(SCOPED), ("input", "dispatch", "metrics_read"))
    lo = min(h[0] for h in raw["host"])
    hi = max(h[0] + h[1] for h in raw["host"])
    return dict(raw, window=[lo, hi])


def test_a_chip_trace_names_the_matmul_attention(scoped):
    paths = scoped["scopes"]["/device:TPU:0"]
    matmuls = {op: p for op, p in paths.items() if "dot_general" in p}
    assert matmuls
    for op, p in matmuls.items():
        assert scopes.scope_of(p, scopes.STEP_SCOPES) == "attention", (op, p)
    assert any("jvp(attention)/" in p for p in matmuls.values())
    assert any("transpose(jvp(attention))/" in p for p in matmuls.values())
    secs = scopes.scope_seconds(scoped, scopes.STEP_SCOPES)
    # XLA fuses the update into the backward matmul, and a fusion carries
    # one path: no op is left in the optimizer scope
    assert set(secs) == {"attention", "unscoped"}
    assert secs["attention"] > 0
    assert sum(secs.values()) == pytest.approx(
        sum(trace.op_seconds(scoped).values()))


def test_a_chip_trace_bounds_its_clock(scoped):
    clock = scopes.clock_offset_ns(scoped)["/device:TPU:0"]
    lo, hi = clock["bracket"]
    assert clock["pairs"] == 6
    assert 0 < lo < clock["offset"] < hi < lo + 0.5e6


def test_summary_of_a_chip_trace(scoped):
    got = scopes.summary(scoped)
    assert got["steps"] == 3
    ms = got["scope_ms_per_step"]
    assert ms["attention"] > 0 and ms["mlp"] == ms["vocab"] == 0
    assert sum(ms.values()) == pytest.approx(
        sum(trace.op_seconds(scoped).values()) / 3 * 1e3)
    assert {op for op, _, _ in got["unscoped_ops"]} == set(
        scopes.scope_op_seconds(scoped, scopes.STEP_SCOPES)["unscoped"])
    assert got["clock_pairs"] == 6
    assert sum(v for _, v in got["idle_gaps_aligned"]) == pytest.approx(
        sum(v for _, v in got["idle_gaps"]))


def test_the_command_reduces_a_launcher_capture(tmp_path, capsys):
    from repro.launch import train
    train.run(train.parse_args(
        ["--arch", "qwen2-0.5b", "--reduced", "--steps", "4", "--batch", "2",
         "--seq", "32", "--scadles", "--trace-dir", str(tmp_path)]))
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert len(files) == 1
    capsys.readouterr()
    assert scopes.main([str(files[0])]) == 0
    got = json.loads(capsys.readouterr().out)
    # the window runs from the first captured step to the last; the CPU
    # has no TPU plane, so nothing is scoped and no clock is bounded
    assert got["steps"] == 3 and got["window_s"] > 0
    assert got["scope_ms_per_step"] is None
    assert got["clock_pairs"] == 0 and got["clock_bracket_ms"] is None


def test_the_command_needs_a_window():
    with pytest.raises(RuntimeError, match="window"):
        scopes.load(str(SCOPED))
