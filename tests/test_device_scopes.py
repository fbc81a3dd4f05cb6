"""Device scopes: the ``qt.*`` names in the fused programs' compiled text,
the scope table built from it, and the host spans' profiler annotation.

CPU, toy sizes: what is checked is names and bookkeeping, never a time.
The TPU compiler's spelling of the same program is checked in
``tests/test_aot_compile.py`` (the one file that describes the topology).
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from quiver_tpu import Feature, GraphSageSampler, telemetry
from quiver_tpu.models import GraphSAGE
from quiver_tpu.parallel import TrainState
from quiver_tpu.pipeline import (make_fused_eval_fn, make_fused_train_step,
                                 make_scan_epoch)
from quiver_tpu.telemetry import noop
from quiver_tpu.telemetry.device_scopes import (
    FEATURE_GATHER, FLOW, MODEL, OPTIMIZER, SAMPLER, instruction_key,
    parse_hlo_scopes, register_program, sampler_hop, scoped)
from quiver_tpu.utils.synthetic import community_graph

# the module itself: ``telemetry.device_scopes`` is the function
ds = sys.modules["quiver_tpu.telemetry.device_scopes"]

FANOUT, B = [4, 3, 2], 16


@pytest.fixture
def fresh(monkeypatch):
    """An empty registry for the test, the process's own put back after."""
    monkeypatch.setattr(ds, "_programs", {})
    monkeypatch.setattr(ds, "_tables", {})


@pytest.fixture(scope="module")
def toy():
    topo, feat, comm = community_graph(300, 4, seed=5)
    feature = Feature(device_cache_size="1G").from_cpu_tensor(feat)
    sampler = GraphSageSampler(topo, FANOUT)
    model = GraphSAGE(hidden=16, out_dim=4, num_layers=3, dropout=0.5)
    seeds = jnp.arange(B, dtype=jnp.int32)
    b0 = sampler.sample(np.asarray(seeds))
    params = model.init(jax.random.PRNGKey(0), feature[b0.n_id], b0.layers)
    labels = jnp.asarray(np.asarray(comm)[:B])

    def apply_fn(p, x, blocks, train=False, rngs=None):
        return model.apply(p, x, blocks, train=train, rngs=rngs)

    return sampler, feature, apply_fn, params, seeds, labels


def _train(toy, steps=1):
    sampler, feature, apply_fn, params, seeds, labels = toy
    tx = optax.adam(1e-2)
    step = make_fused_train_step(sampler, feature, apply_fn, tx)
    state = TrainState.create(jax.tree_util.tree_map(jnp.copy, params), tx)
    for i in range(steps):
        state, loss = step(state, seeds, labels, jnp.ones((B,), bool),
                           jax.random.PRNGKey(i))
    assert np.isfinite(float(loss))
    return step, state


# ------------------------------------------------------------ the program
def test_train_step_table_names_every_layer(fresh, toy):
    _train(toy)
    table = telemetry.device_scopes()["jit_qt_fused_train_step"]
    names = set(table.values())

    def under(scope, backward=None):
        return [n for n in names if scope + "/" in n or scope + ")" in n
                if backward is None or ("transpose(" in n) == backward]

    for n in range(1, len(FANOUT) + 1):
        assert under(sampler_hop(n)), f"no instruction under hop {n}"
    assert not under(sampler_hop(len(FANOUT) + 1))
    assert under(SAMPLER) and under(FEATURE_GATHER) and under(OPTIMIZER)
    assert under(MODEL, backward=False), "no forward pass under qt.model"
    assert under(MODEL, backward=True), "no backward pass under qt.model"
    # flax names its modules beneath the scope: nothing added in models/
    assert any("GraphSAGE/conv0" in n for n in under(MODEL))
    # only the model runs twice
    assert not [n for n in names if "transpose(" in n and MODEL not in n]
    # keys are HLO lines up to the opcode
    assert all(instruction_key(k + " fusion(%x)") == k for k in table)


def test_gat_conv_carries_both_part_scopes_in_both_passes(fresh, toy):
    """The published GAT (``models.GNN``) through the fused step: the
    convolution's product(s) and the frame's ``skip`` sit under
    ``qt.model.project``, scores, softmax and weighted sum under
    ``qt.model.attention``, each nested under ``qt.model`` and in both
    passes: what the ``rel_*`` readers and ``attention_roofline.train``
    of the benchmark key on."""
    from quiver_tpu.models import GNN, rgnn_apply_fn
    from quiver_tpu.telemetry.device_scopes import (MODEL_ATTENTION,
                                                    MODEL_PROJECT)

    sampler, feature, _, _, seeds, labels = toy
    model = GNN(hidden=16, out_dim=4, num_layers=3, heads=2)
    b0 = sampler.sample(np.asarray(seeds))
    v = model.init(jax.random.PRNGKey(0), feature[b0.n_id], b0.layers,
                   b0.n_id, b0.n_id_mask)
    tx = optax.adam(1e-2)
    step = make_fused_train_step(sampler, feature, rgnn_apply_fn(model), tx)
    state = TrainState.create({"params": v["params"]}, tx,
                              {"batch_stats": v["batch_stats"]})
    state, loss = step(state, seeds, labels, jnp.ones((B,), bool),
                       jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    names = set(telemetry.device_scopes()["jit_qt_fused_train_step"].values())
    for part in (MODEL_PROJECT, MODEL_ATTENTION):
        for backward in (False, True):
            found = [n for n in names if f"GNN/conv0/{part}/" in n
                     and ("transpose(" in n) == backward]
            assert found, (part, backward)
            # nested: the layer's name comes first, the part's last
            assert all(n.index(MODEL + ")") < n.index(part) for n in found)
    assert any(f"GNN/{MODEL_PROJECT}/skip0" in n for n in names)
    assert any("lin/dot_general" in n and MODEL_PROJECT in n for n in names)
    assert not any(MODEL_ATTENTION in n and "lin/dot_general" in n
                   for n in names)


def test_eval_and_scan_programs_carry_their_own_names(fresh, toy):
    sampler, feature, apply_fn, params, seeds, labels = toy
    ev = make_fused_eval_fn(sampler, feature, apply_fn)
    ev(params, seeds, jax.random.PRNGKey(3))
    tx = optax.adam(1e-2)
    epoch = make_scan_epoch(sampler, feature, apply_fn, tx)
    epoch(TrainState.create(jax.tree_util.tree_map(jnp.copy, params), tx),
          jnp.stack([seeds, seeds]), jnp.stack([labels, labels]),
          jax.random.PRNGKey(4))
    tables = telemetry.device_scopes()
    assert set(tables) == {"jit_qt_fused_eval", "jit_qt_scan_epoch"}
    ev_names = set(tables["jit_qt_fused_eval"].values())
    assert any(MODEL in n for n in ev_names)
    assert not any(OPTIMIZER in n or "transpose(" in n for n in ev_names)
    scan_names = set(tables["jit_qt_scan_epoch"].values())
    for scope in (sampler_hop(1), FEATURE_GATHER, MODEL, OPTIMIZER):
        assert any(scope in n for n in scan_names), scope


def test_registry_keeps_no_array_and_registers_once(fresh, toy, monkeypatch):
    calls = []
    real = ds.register_program
    import quiver_tpu.pipeline as pipeline

    monkeypatch.setattr(pipeline, "register_program",
                        lambda j, a: (calls.append(j), real(j, a)))
    _train(toy, steps=3)
    assert len(calls) == 1, "later calls must not register again"
    (name, (jitted, abstract)), = ds._programs.items()
    assert name == "jit_qt_fused_train_step" == "jit_" + jitted.__name__
    leaves = jax.tree_util.tree_leaves(abstract)
    assert leaves and all(type(x) is jax.ShapeDtypeStruct for x in leaves)
    # the table the step reads is an argument, so its shape is in the entry
    feat = toy[1]
    assert any(x.shape == tuple(feat.shape) for x in leaves)
    # a sharding is kept only where the call saw a committed array ...
    dev = jax.devices()[0]
    put = ds._abstract(jax.device_put(jnp.ones(3), dev))
    assert put.sharding == jax.sharding.SingleDeviceSharding(dev)
    assert ds._abstract(jnp.ones(3)).sharding is None
    assert ds._abstract(np.ones(3)).sharding is None
    # ... so the text comes from the call's own executable: nothing compiles
    builds = []
    watching = [True]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: builds.append(event)
        if watching[0] and event.endswith("backend_compile_duration")
        else None)
    assert scoped(telemetry.device_scopes()["jit_qt_fused_train_step"])
    watching[0] = False
    assert builds == []


def test_newest_registration_of_a_name_wins(fresh):
    @jax.jit
    def qt_toy(x):
        with jax.named_scope(MODEL):
            return x * 2

    register_program(qt_toy, (np.ones((4,), np.float32),))
    first = telemetry.device_scopes()["jit_qt_toy"]
    assert telemetry.device_scopes()["jit_qt_toy"] == first    # memoised
    register_program(qt_toy, (np.ones((8, 8), np.float32),))
    second = telemetry.device_scopes()["jit_qt_toy"]
    assert any("f32[8,8]" in k for k in second)
    assert not any("f32[4]" in k for k in second)
    assert type(ds._programs["jit_qt_toy"][1][0]) is jax.ShapeDtypeStruct


def test_device_scopes_does_not_raise_when_lower_does(fresh, capsys):
    class Broken:
        __name__ = "qt_broken"

        def lower(self, *a):
            raise RuntimeError("no lowering here")

    register_program(Broken(), (np.zeros(3),))
    assert telemetry.device_scopes() == {}
    assert telemetry.device_scopes() == {}
    err = capsys.readouterr().err
    assert err.count("jit_qt_broken") == 1 and "no lowering here" in err
    # nor does registering something that is no program
    register_program(object(), (np.zeros(3),))
    assert "could not register" in capsys.readouterr().err


def test_stale_names_compile_once_more_without_the_cache(fresh, capsys,
                                                         monkeypatch):
    asked = []
    texts = {True: STALE_TEXT, False: TPU_TEXT}

    def fake(jitted, abstract, cache):
        asked.append(cache)
        return texts[cache]

    monkeypatch.setattr(ds, "_compiled_text", fake)
    register_program(jax.jit(lambda x: x), (np.zeros(3),))
    (table,) = telemetry.device_scopes().values()
    assert asked == [True, False]
    assert scoped(table) > 0
    assert "once more with the cache off" in capsys.readouterr().err


STALE_SCRIPT = """
import sys
import jax, jax.numpy as jnp
from quiver_tpu import telemetry

@jax.jit
def qt_same(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.tanh(x) * 2

x = jnp.ones((8, 8))
telemetry.register_program(qt_same, (x,))
qt_same(x)
names = set(telemetry.device_scopes().get("jit_qt_same", {}).values())
print("NAMES", sorted(n for n in names if "tanh" in n))
"""


def test_scopes_renamed_under_a_filled_cache_are_read_fresh(tmp_path):
    """Two builds of one program that differ in a scope name only share a
    persistent-cache entry (the key strips debug information), and the
    second reads the first's names back: from the cache, and from the
    executable its own call holds in memory.  ``device_scopes`` sees that
    no ``qt.`` name is left and compiles past both."""
    import os
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # the cache is placed from outside, as a deployment places it
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")

    def build(scope):
        r = subprocess.run(
            [sys.executable, "-c", STALE_SCRIPT, scope],
            env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        (line,) = [l for l in r.stdout.splitlines() if l.startswith("NAMES")]
        return line, r.stderr

    line, _ = build("before.rename")
    assert "before.rename/tanh" in line
    line, err = build("qt.after")
    assert "qt.after/tanh" in line and "before.rename" not in line, line
    assert "once more with the cache off" in err


def test_cache_is_back_on_after_a_compile_without_it(fresh):
    was = jax.config.jax_enable_compilation_cache

    @jax.jit
    def qt_plain(x):
        return x + 1

    text = ds._compiled_text(qt_plain, (jax.ShapeDtypeStruct((2,), "f4"),),
                             cache=False)
    assert "HloModule jit_qt_plain" in text
    assert jax.config.jax_enable_compilation_cache == was


# -------------------------------------------------------------- the parser
# cut from a v5e compile of a gather + matmul step (``as_text()``)
TPU_TEXT = '''\
HloModule jit_small_step, is_scheduled=true, entry_computation_layout={(f32[4096,128]{1,0:T(8,128)})->f32[]{:T(128)}}

%fused_computation (param_0.2: f32[4096,128], param_1.4: s32[2048]) -> bf16[2048,128] {
  %param_0.2 = f32[4096,128]{1,0:T(8,128)S(1)} parameter(0)
  %gather.3 = bf16[2048,128]{1,0:T(8,128)(2,1)} gather(%param_0.2, %param_1.4), offset_dims={1}, metadata={op_name="jit(small_step)/qt.feature.gather/jit(_take)/gather" stack_frame_id=3}
  ROOT %reshape.7 = bf16[2048,128]{1,0:T(8,128)(2,1)S(1)} reshape(%gather.3), metadata={op_name="jit(small_step)/qt.feature.gather/jit(_take)/gather" stack_frame_id=3}
}

%bitcast_fusion (bitcast_input: f32[128,128]) -> f32[128,128] {
  %bitcast_input = f32[128,128]{1,0:T(8,128)S(1)} parameter(0)
  ROOT %bitcast = f32[128,128]{1,0:T(8,128)} bitcast(%bitcast_input)
}

%fused_computation.2 (param_0.26: f32[128,128], param_1.36: bf16[2048,128]) -> f32[] {
  %param_1.36 = bf16[2048,128]{1,0:T(8,128)(2,1)S(1)} parameter(1)
  %convolution.3 = f32[2048,128]{1,0:T(8,128)} convolution(%param_1.36, %param_0.26), dim_labels=bf_io->bf, metadata={op_name="jit(small_step)/jvp(qt.model)/dot_general" stack_frame_id=4}
  %tanh.3 = f32[2048,128]{1,0:T(8,128)} tanh(%convolution.3), metadata={op_name="jit(small_step)/jvp(qt.model)/tanh" stack_frame_id=2}
  ROOT %reduce_sum.0 = f32[]{:T(128)} reduce(%tanh.3, %constant.33), dimensions={0,1}, to_apply=%region_1.4, metadata={op_name="jit(small_step)/jvp(qt.model)/tanh" stack_frame_id=2}
}

ENTRY %main.5 (table.1: f32[4096,128], idx.1: s32[2048], w.1: f32[128,128]) -> f32[] {
  %table.1 = f32[4096,128]{1,0:T(8,128)} parameter(0), sharding={replicated}, metadata={op_name="table"}
  %copy-start = (f32[4096,128]{1,0:T(8,128)S(1)}, f32[4096,128]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%table.1), cross_program_prefetch_index=0
  %copy-done = f32[4096,128]{1,0:T(8,128)S(1)} copy-done(%copy-start)
  %bitcast.9 = f32[128,128]{1,0:T(8,128)} bitcast(%w.1)
  %fusion = bf16[2048,128]{1,0:T(8,128)(2,1)S(1)} fusion(%copy-done, %idx.1), kind=kCustom, calls=%fused_computation, metadata={op_name="jit(small_step)/qt.feature.gather/jit(_take)/gather" stack_frame_id=3}, backend_config={"flag_configs":[],"scoped_memory_configs":[]}
  %fusion.7 = f32[128,128]{1,0:T(8,128)} fusion(%bitcast.9), kind=kLoop, calls=%bitcast_fusion
  %fusion.8 = (f32[]{:T(128)}, /*index=1*/f32[2048,128]{1,0:T(8,128)}) fusion(%fusion.7, %fusion), kind=kOutput, calls=%fused_computation.2, backend_config={"flag_configs":[]}
  ROOT %fusion.1 = f32[]{:T(128)} fusion(%fusion.7, %fusion), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(small_step)/transpose(jvp(qt.model))/dot_general" stack_frame_id=4}, backend_config={"flag_configs":[]}
}
'''

# the same program as a build before the scopes would have left it in the
# compile cache
STALE_TEXT = TPU_TEXT.replace("qt.feature.gather/", "").replace(
    "jvp(qt.model)", "jvp()")


def test_parser_on_a_tpu_text():
    module, table = parse_hlo_scopes(TPU_TEXT)
    assert module == "jit_small_step"
    # a fusion takes its own op_name ...
    assert table["%fusion = bf16[2048,128]{1,0:T(8,128)(2,1)S(1)}"] == (
        "jit(small_step)/qt.feature.gather/jit(_take)/gather")
    assert table["%fusion.1 = f32[]{:T(128)}"].endswith(
        "transpose(jvp(qt.model))/dot_general")
    # ... and without one the commonest of the computation it calls (a
    # tuple shape is a key without the printer's index comments)
    assert table["%fusion.8 = (f32[]{:T(128)}, f32[2048,128]{1,0:T(8,128)})"
                 ] == "jit(small_step)/jvp(qt.model)/tanh"
    # what has neither maps to nothing
    for left_out in ("%bitcast.9 = ", "%copy-done = ", "%copy-start = ",
                     "%fusion.7 = ", "%bitcast = "):
        assert not [k for k in table if k.startswith(left_out)], left_out
    # a parameter's op_name is its argument's name: kept, never a scope
    assert table["%table.1 = f32[4096,128]{1,0:T(8,128)}"] == "table"
    assert scoped(table) == len(table) - 1


# cut from a v5e compile of ``models.RGNN``'s grouped projection: the
# compiler writes ``lax.ragged_dot`` as kernels of its own and names them
# itself, so the traced op_name, scopes and all, is gone from them
RENAMED_TEXT = '''\
HloModule jit_qt_fused_train_step, is_scheduled=true

ENTRY %main.9 (x.1: f32[512,64], w.1: f32[5,64,128], gs.1: s32[5]) -> f32[5,64,128] {
  %x.1 = f32[512,64]{1,0:T(8,128)} parameter(0), metadata={op_name="x"}
  %w.1 = f32[5,64,128]{2,1,0:T(8,128)} parameter(1), metadata={op_name="w"}
  %fusion.2 = f32[512,64]{1,0:T(8,128)} fusion(%x.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(qt_fused_train_step)/jvp(qt.model)/RGNN/conv0/qt.model.project/jit(_take)/gather"}
  %fusion.3 = s32[5]{0:T(128)} fusion(%gs.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(qt_fused_train_step)/jvp(qt.model)/RGNN/conv0/qt.model.project/jit(bincount)/scatter-add"}
  %bitcast.4 = s32[5]{0:T(128)} bitcast(%fusion.3)
  %ragged-dot-metadata = (s32[6]{0:T(128)}, s32[8]{0:T(128)}) custom-call(%bitcast.4), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %get-tuple-element = s32[6]{0:T(128)} get-tuple-element(%ragged-dot-metadata), index=0
  %ragged-dot-none.1 = f32[512,128]{1,0:T(8,128)} custom-call(%get-tuple-element, %fusion.2, %w.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.5 = f32[512,128]{1,0:T(8,128)} fusion(%ragged-dot-none.1), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(qt_fused_train_step)/transpose(jvp(qt.model))/RGNN/conv0/qt.model.project/jit(_take)/gather"}
  %copy.6 = f32[512,128]{1,0:T(8,128)} copy(%fusion.5)
  ROOT %ragged-dot-none = f32[5,64,128]{2,1,0:T(8,128)} custom-call(%get-tuple-element, %fusion.2, %copy.6), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %ragged-dot-none.7 = f32[512,128]{1,0:T(8,128)} custom-call(%x.1, %w.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
}
'''


def test_a_kernel_the_compiler_renamed_takes_its_operands_scopes():
    _, table = parse_hlo_scopes(RENAMED_TEXT)
    scope = "jit(qt_fused_train_step)/%s/RGNN/conv0/qt.model.project/"
    # forward: what it reads was made in the forward pass
    assert table["%ragged-dot-none.1 = f32[512,128]{1,0:T(8,128)}"] == (
        scope % "jvp(qt.model)" + "jit(_take)/ragged-dot-none")
    # backward: one operand is a cotangent (found through a ``copy``),
    # and that one wins over the forward operand before it
    assert table["%ragged-dot-none = f32[5,64,128]{2,1,0:T(8,128)}"] == (
        scope % "transpose(jvp(qt.model))" + "jit(_take)/ragged-dot-none")
    # the kernel that prepares the groups, through a ``bitcast``
    assert table["%ragged-dot-metadata = (s32[6]{0:T(128)}, "
                 "s32[8]{0:T(128)})"] == (
        scope % "jvp(qt.model)" + "jit(bincount)/ragged-dot-metadata")
    # nothing scoped among its operands: left as the compiler wrote it
    assert table["%ragged-dot-none.7 = f32[512,128]{1,0:T(8,128)}"] == (
        "ragged-dot-none")


# cut from a v5e compile of a ``blocked`` hop: the ``lax.cond`` between
# the window fetch and the per-draw fetch, and a ``while``
FLOW_TEXT = '''\
HloModule jit_qt_fused_train_step, is_scheduled=true

%region_2.12 (arg_tuple.1: (s32[1024,5], s32[4096,128])) -> (s32[1024,5]) {
  %fusion.14 = s32[1024,128]{1,0:T(8,128)} fusion(%get-tuple-element.88, %get-tuple-element.212), kind=kCustom, calls=%fused_computation.14, metadata={op_name="jit(qt_fused_train_step)/qt.sampler.hop3/jit(sample_neighbors)/cond/branch_1_fun/gather"}
}

ENTRY %main.9 (t.1: s32[4096,128]) -> s32[1024,5] {
  %conditional.2 = (s32[1024,5]{0,1:T(8,128)}) conditional(%convert_element_type.29, %tuple.28, %tuple.29), branch_computations={%region_5.26, %region_2.12}, metadata={op_name="jit(qt_fused_train_step)/qt.sampler.hop3/jit(sample_neighbors)/cond" stack_frame_id=20}
  %while.3 = (s32[]{:T(128)}, f32[8]{0:T(256)}) while(%tuple.30), condition=%cond.4, body=%body.5
}
'''


def test_a_conditional_is_no_layers_own_time():
    """Its event spans its branch's events, which the trace lists too: the
    table keeps it out of every layer, under ``qt.flow``, and the branch's
    operations under the scope they were traced in."""
    _, table = parse_hlo_scopes(FLOW_TEXT)
    assert table["%conditional.2 = (s32[1024,5]{0,1:T(8,128)})"] == (
        "qt.flow/jit(qt_fused_train_step)/qt.sampler.hop3/"
        "jit(sample_neighbors)/cond")
    assert table["%while.3 = (s32[]{:T(128)}, f32[8]{0:T(256)})"] == (
        FLOW + "/")
    assert "/qt.sampler.hop3/" in table[
        "%fusion.14 = s32[1024,128]{1,0:T(8,128)}"]
    assert scoped(table) == 3


def test_a_text_without_scope_names_is_stale():
    _, table = parse_hlo_scopes(STALE_TEXT)
    assert table and scoped(table) == 0
    assert parse_hlo_scopes("")[1] == {}


def test_key_of_a_trace_event_is_the_key_of_the_text_line():
    # a trace prints operands with their shapes, the text bare
    event = ("%fusion.5 = s32[2048]{0:T(1024)S(1)} fusion(s32[2048]"
             "{0:T(1024)} %idx.1), kind=kLoop, calls=%fused_computation.8")
    line = ("  ROOT %fusion.5 = s32[2048]{0:T(1024)S(1)} fusion(%idx.1), "
            "kind=kLoop, calls=%fused_computation.8, metadata={op_name=\"x\"}")
    assert (instruction_key(event) == instruction_key(line)
            == "%fusion.5 = s32[2048]{0:T(1024)S(1)}")
    assert instruction_key("ENTRY %main.5 (a: f32[2]) -> f32[] {") is None
    assert instruction_key("}") is None


# --------------------------------------------------------------- host spans
@pytest.fixture
def telemetry_on():
    telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.set_enabled(True)
    telemetry.reset()


def test_span_aggregates_and_annotates_the_profiler(telemetry_on,
                                                    monkeypatch):
    import quiver_tpu.telemetry.spans as spans

    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(spans, "_trace_annotation", lambda: Annotation)
    with telemetry.span("sampler.sample"):
        with telemetry.span("feature.getitem"):
            pass
    assert seen == [("enter", "qt.sampler.sample"),
                    ("enter", "qt.feature.getitem"),
                    ("exit", "qt.feature.getitem"),
                    ("exit", "qt.sampler.sample")]
    summary = telemetry.get_tracer().summary()
    assert summary["sampler.sample"]["count"] == 1
    assert summary["feature.getitem"]["count"] == 1


def test_span_uses_jax_profiler_and_is_the_noop_when_off(telemetry_on):
    import quiver_tpu.telemetry.spans as spans

    assert spans._trace_annotation() is jax.profiler.TraceAnnotation
    with telemetry.span("real"):    # the real class, no trace running
        pass
    assert telemetry.get_tracer().summary()["real"]["count"] == 1
    telemetry.set_enabled(False)
    assert telemetry.span("anything") is noop.SPAN
