"""The step program's named scopes (``train/step.py::STEP_SCOPES``), the
table that files a compiled step's instructions under them
(``scope_table``), and ``train/loop.py::compiled_step``.

On the CPU mesh at ``resnet_test`` size: which slice an instruction lands
in is a property of the program's metadata, not of the backend.
"""

import contextlib
import gc
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.comm import CommConfig
from batchai_retinanet_horovod_coco_tpu.comm.compress import init_comm_state
from batchai_retinanet_horovod_coco_tpu.data.pipeline import Batch
from batchai_retinanet_horovod_coco_tpu.models import RetinaNetConfig, build_retinanet
from batchai_retinanet_horovod_coco_tpu.models.deepseek_v2 import DeepseekV2
from batchai_retinanet_horovod_coco_tpu.models.granite_hybrid import GraniteHybrid
from batchai_retinanet_horovod_coco_tpu.models.olmo_hybrid import OlmoHybrid
from batchai_retinanet_horovod_coco_tpu.obs import trace
from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh, zero
from batchai_retinanet_horovod_coco_tpu.parallel.mesh import DATA_AXIS
from batchai_retinanet_horovod_coco_tpu.train import create_train_state, loop
from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer
from batchai_retinanet_horovod_coco_tpu.train.step import (
    STEP_SCOPES,
    UNSCOPED,
    make_train_step,
    scope_of,
    scope_table,
)
from batchai_retinanet_horovod_coco_tpu.train.task import DetectionTask, LMTask

# The vocabulary is one; a step enters its task's scopes and every step's.
EVERY_STEPS = ("optimizer", "grad_allreduce")
DETECTION_SCOPES = (*DetectionTask.scopes, *EVERY_STEPS)
# the language-model task's are its model's (LMTask: ``model.scopes``)
LM_SCOPES = (*GraniteHybrid.scopes, *DeepseekV2.scopes, *OlmoHybrid.scopes)

HW = (64, 64)
NUM_CLASSES = 3
BATCH = 4
MODEL_SLICES = ("backbone", "fpn", "heads")
COLLECTIVE = re.compile(r"^(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)")


def _model():
    return build_retinanet(RetinaNetConfig(
        num_classes=NUM_CLASSES, backbone="resnet_test", norm_kind="frozen_bn",
        fpn_channels=16, head_width=16, head_depth=1, dtype=jnp.float32))


def _optimizer(**kw):
    return make_optimizer(OptimizerConfig(schedule="constant", warmup_steps=0), **kw)[0]


def _batch_arrays():
    return dict(
        images=jnp.zeros((BATCH, *HW, 3), jnp.uint8),
        gt_boxes=jnp.tile(jnp.asarray([[8.0, 8.0, 40.0, 40.0]]), (BATCH, 2, 1)),
        gt_labels=jnp.ones((BATCH, 2), jnp.int32),
        gt_mask=jnp.ones((BATCH, 2), bool),
    )


def _host_batches():
    arrays = {k: np.asarray(v) for k, v in _batch_arrays().items()}
    while True:
        yield Batch(**arrays, image_ids=np.arange(BATCH, dtype=np.int64),
                    scales=np.ones((BATCH,), np.float32), valid=np.ones((BATCH,), bool))


def _instructions(compiled) -> dict[str, str]:
    """instruction name -> opcode, from the optimized module's text."""
    out = {}
    for line in compiled.as_text().splitlines():
        # the first "word(" after the "=": a result shape has none, tuple or not
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(", line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


@pytest.fixture(scope="module")
def compiled_steps():
    """One compiled step per flavor: single device, and data-parallel,
    ZeRO and compressed-gradient steps over four virtual devices."""
    model = _model()
    batch = _batch_arrays()
    state = create_train_state(model, _optimizer(), (1, *HW, 3), jax.random.key(0))
    mesh = make_mesh(4)
    out = {"single": make_train_step(model, HW, NUM_CLASSES, donate_state=False).lower(state, batch).compile()}
    out["dp4"] = make_train_step(model, HW, NUM_CLASSES, mesh=mesh, donate_state=False).lower(state, batch).compile()
    comm = CommConfig(compress="int8")
    comm_state = {k: jnp.asarray(v) for k, v in init_comm_state(state.params, comm, 4).items()}
    out["comm4"] = make_train_step(model, HW, NUM_CLASSES, mesh=mesh, comm=comm, donate_state=False).lower(
        state.replace(comm_state=comm_state), batch).compile()
    tx = _optimizer(shard_clip_axis=DATA_AXIS)
    zstate = create_train_state(model, tx, (1, *HW, 3), jax.random.key(0), init_opt_state=False)
    zstate = zstate.replace(opt_state=zero.init_sharded_opt_state(tx, zstate.params, mesh))
    out["zero4"] = make_train_step(model, HW, NUM_CLASSES, mesh=mesh, shard_weight_update=True,
                                   donate_state=False).lower(zstate, batch).compile()
    return out


@pytest.mark.parametrize("op_name,expected", [
    ("jit(train_step)/jvp(RetinaNet)/backbone/backbone/stage2/stage2_block0/conv1/conv_general_dilated",
     ("backbone", "fwd", "backbone/backbone/stage2/stage2_block0/conv1/conv_general_dilated")),
    ("jit(train_step)/transpose(jvp(RetinaNet))/heads/cls/cls_head/logits/conv_general_dilated",
     ("heads", "bwd", "heads/cls/cls_head/logits/conv_general_dilated")),
    # the transform wraps whichever scope comes first after it
    ("jit(train_step)/jvp(loss)/jit(softplus)/log1p", ("loss", "fwd", "loss/softplus/log1p")),
    ("jit(train_step)/transpose(jvp(loss))/mul", ("loss", "bwd", "loss/mul")),
    ("jit(sharded_step)/shard_map/grad_allreduce/psum", ("grad_allreduce", "fwd", "grad_allreduce/psum")),
    # the OUTERMOST vocabulary scope wins
    ("jit(zero_step)/shard_map/optimizer/grad_allreduce/all_gather", ("optimizer", "fwd", "optimizer/grad_allreduce/all_gather")),
    ("jit(train_step)/jvp(assign)/jit(assign_fused)/assign_fused", ("assign", "fwd", "assign/assign_fused/assign_fused")),
    ("jit(train_step)/convert_element_type", (UNSCOPED, "fwd", "train_step/convert_element_type")),
    ("", (UNSCOPED, "fwd", "")),
])
def test_scope_of_files_an_op_name_under_its_outermost_scope(op_name, expected):
    assert scope_of(op_name) == expected


def test_scope_table_reads_a_kernel_call_whose_text_runs_over_several_lines():
    """As the v5e compiler prints a Pallas call that was given ``metadata=``
    (the attention kernel, ops/attention.py): the frontend attributes hold
    newlines, and ``metadata={op_name=...}`` opens the third line."""

    class Compiled:
        def as_text(self):
            return "\n".join([
                "ENTRY %main.1 (p: bf16[8]) -> bf16[8] {",
                '  %copy.1 = bf16[8]{0} copy(%p)',
                '  %splash_mha_dq.1 = (f32[8,64]{1,0}, bf16[8]{0}) custom-call(%copy.1), '
                'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={',
                '"xprof_metadata":"{\\"block_q_dq\\": 1024}"',
                '}}, metadata={op_name="jit(train_step)/transpose(jvp(jvp()))/checkpoint/attention/'
                'vmap(jit(_splash_attention))/splash_mha_dq/pallas_call" stack_frame_id=229}, backend_config={}',
                '  %fusion.2 = bf16[8]{0} fusion(%copy.1), kind=kLoop, calls=%f, '
                'metadata={op_name="jit(train_step)/jvp(mlp)/mul"}',
                '  ROOT %copy.3 = bf16[8]{0} copy(%fusion.2)',
                "}",
                '}}, metadata={op_name="jit(train_step)/jvp(mlp)/nobody"}',
            ])

    table = scope_table(Compiled())
    assert table["splash_mha_dq.1"] == ("attention", "bwd", "attention/_splash_attention/splash_mha_dq/pallas_call")
    assert table["fusion.2"] == ("mlp", "fwd", "mlp/mul")
    assert table["copy.1"][0] == table["copy.3"][0] == UNSCOPED  # a later line's metadata is not theirs


@pytest.mark.parametrize("flavor", ["single", "dp4", "comm4", "zero4"])
def test_every_convolution_is_filed_under_the_model(compiled_steps, flavor):
    compiled = compiled_steps[flavor]
    table = scope_table(compiled)
    convs = [table[n] for n, op in _instructions(compiled).items() if op == "convolution"]
    assert len(convs) > 30
    # XLA:CPU rewrites some weight-gradient convolutions into instructions
    # without metadata (empty path): those are unscoped, never misfiled.
    named = [c for c in convs if c[2]]
    assert len(named) >= 0.7 * len(convs)
    assert {s for s, _, _ in named} == set(MODEL_SLICES)
    assert {s for s, _, path in convs if not path} <= {UNSCOPED}
    # forward and backward of each
    for s in MODEL_SLICES:
        assert {d for t, d, _ in named if t == s} == {"fwd", "bwd"}, s


@pytest.mark.parametrize("flavor", ["single", "dp4", "comm4", "zero4"])
def test_every_scope_of_the_vocabulary_reaches_the_compiled_step(compiled_steps, flavor):
    table = scope_table(compiled_steps[flavor])
    filed = {(s, d) for s, d, _ in table.values()}
    on_a_mesh = flavor != "single"
    assert set(STEP_SCOPES) == {*DetectionTask.scopes, *LM_SCOPES, *EVERY_STEPS}
    assert not set(LM_SCOPES) - {"loss"} & {s for s, _ in filed}
    for s in DETECTION_SCOPES:
        if s == "grad_allreduce" and not on_a_mesh:
            assert not {d for t, d in filed if t == s}
            continue
        assert (s, "fwd") in filed, s
    # what is differentiated has a backward; targets (stop_gradient), the
    # update and the reduction of finished gradients have none
    assert {s for s, d in filed if d == "bwd"} == {*MODEL_SLICES, "loss"}
    for s in DETECTION_SCOPES:
        paths = {p for t, _, p in table.values() if t == s}
        for name in STEP_SCOPES[s]:
            assert any(f"/{name}/" in p for p in paths), (s, name)


def _lm_state_and_batch():
    from batchai_retinanet_horovod_coco_tpu.models import granite_hybrid

    model = granite_hybrid.GraniteHybrid(granite_hybrid.TINY)
    tx = make_optimizer(OptimizerConfig(optimizer="adamw", schedule="constant", warmup_steps=0))[0]
    state = create_train_state(model, tx, (1, 8), jax.random.key(0), example_dtype=LMTask.example_dtype)
    seg = jnp.asarray(np.repeat([[0, 1, 2]], [20, 30, 14], axis=1), jnp.int32)
    return model, state, {"tokens": jnp.zeros((1, 64), jnp.int32), "segment_ids": seg}


def test_the_lm_tasks_scopes_reach_the_compiled_step_through_recomputation():
    """Every layer of the LM step is recomputed in its backward pass
    (``jax.checkpoint``): forward, recomputed forward and backward all keep
    the layer's scope, and nothing of detection's is there."""
    model, state, batch = _lm_state_and_batch()
    compiled = make_train_step(model, (1, 64), None, task=LMTask(), donate_state=False).lower(state, batch).compile()
    table = scope_table(compiled)
    filed = {(s, d) for s, d, _ in table.values()}
    for s in (*model.scopes, "optimizer"):
        assert (s, "fwd") in filed, s
    assert {s for s, d in filed if d == "bwd"} >= {"embed", "mamba", "attention", "mlp", "lm_head", "loss"}
    assert not {"mla", "dense_mlp", "moe"} & {s for s, _ in filed}  # the other language model's
    assert not set(DetectionTask.scopes) - {"loss"} & {s for s, _ in filed}
    paths = {p for t, _, p in table.values() if t == "mamba"}
    for name in STEP_SCOPES["mamba"]:
        assert any(f"/{name}/" in p for p in paths), name
    # the dot products of each kind of layer are filed under it
    dots = [table[n] for n, op in _instructions(compiled).items() if op == "dot" and table[n][2]]
    assert {s for s, _, _ in dots} >= {"mamba", "attention", "mlp", "lm_head"}


def test_the_hybrid_expert_models_step_files_every_operation_it_can_under_a_scope_of_the_model():
    """models/nemotron_h.py through the shared step: a layer is ONE mixer, so
    the model enters ``mamba``, ``attention`` and ``moe`` and neither ``mlp``
    nor the other mixture of experts' ``mla`` / ``dense_mlp`` / ``moe/aux``;
    forward, recomputed forward and backward keep the scope; every matrix
    product and every sort of the compiled step lies under one of the
    model's scopes or the optimizer's, none is unscoped."""
    from batchai_retinanet_horovod_coco_tpu.models import nemotron_h
    from batchai_retinanet_horovod_coco_tpu.train.step import UNSCOPED

    model = nemotron_h.NemotronH(nemotron_h.TINY)
    tx = make_optimizer(OptimizerConfig(optimizer="adamw", schedule="constant", warmup_steps=0))[0]
    state = create_train_state(model, tx, (1, 8), jax.random.key(0), example_dtype=LMTask.example_dtype)
    seg = jnp.asarray(np.repeat([[0, 1, 2], [0, 1, 1]], [20, 30, 14], axis=1), jnp.int32)
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32), "segment_ids": seg}
    compiled = make_train_step(model, (2, 64), None, task=LMTask(), donate_state=False).lower(state, batch).compile()
    table = scope_table(compiled)
    filed = {(s, d) for s, d, _ in table.values()}
    assert model.scopes == ("embed", "mamba", "attention", "moe", "lm_head", "loss")
    assert set(model.scopes) <= set(STEP_SCOPES)
    for s in (*model.scopes, "optimizer"):
        assert (s, "fwd") in filed, s
    assert {s for s, d in filed if d == "bwd"} >= set(model.scopes)
    assert not {"mla", "dense_mlp", "mlp"} & {s for s, _ in filed}
    assert not set(DetectionTask.scopes) - {"loss"} & {s for s, _ in filed}
    for slice_, beneath in (("mamba", STEP_SCOPES["mamba"]), ("moe", ("router", "dispatch", "experts", "combine", "shared"))):
        paths = {p for t, _, p in table.values() if t == slice_}
        for name in beneath:
            assert any(f"/{name}/" in p or p.endswith("/" + name) for p in paths), (slice_, name)
    assert not any("/aux" in p for t, _, p in table.values() if t == "moe")  # no balance loss
    work = {n: table[n] for n, op in _instructions(compiled).items() if op in ("dot", "sort", "convolution")}
    assert work and not [n for n, (s, _, _) in work.items() if s == UNSCOPED]
    assert {s for s, _, _ in work.values()} >= {"mamba", "attention", "moe", "lm_head"}
    assert {s for s, _, _ in work.values()} <= {*model.scopes, "optimizer"}


def test_a_step_built_for_an_explicit_detection_task_is_the_default_step():
    model = _model()
    state = create_train_state(model, _optimizer(), (1, *HW, 3), jax.random.key(0))
    batch = _batch_arrays()
    default = make_train_step(model, HW, NUM_CLASSES, donate_state=False).lower(state, batch).as_text()
    explicit = make_train_step(model, HW, None, task=DetectionTask(NUM_CLASSES), donate_state=False)
    assert explicit.lower(state, batch).as_text() == default


@pytest.mark.parametrize("flavor,expected", [
    ("dp4", {"all-reduce"}),
    ("comm4", {"all-reduce", "reduce-scatter", "all-gather"}),
    ("zero4", {"all-reduce", "reduce-scatter", "all-gather"}),
])
def test_every_collective_is_filed_under_grad_allreduce(compiled_steps, flavor, expected):
    compiled = compiled_steps[flavor]
    table = scope_table(compiled)
    collectives = {n: op for n, op in _instructions(compiled).items() if COLLECTIVE.match(op)}
    kinds = {COLLECTIVE.match(op).group(1) for op in collectives.values()}
    assert expected <= kinds, kinds
    assert {table[n][0] for n in collectives} == {"grad_allreduce"}, {
        n: table[n] for n in collectives if table[n][0] != "grad_allreduce"}


def test_scopes_are_metadata_only(monkeypatch):
    """The lowered module is the same text with the scopes and without
    them; only its debug information differs."""
    model = _model()
    state = create_train_state(model, _optimizer(), (1, *HW, 3), jax.random.key(0))
    batch = _batch_arrays()
    mesh = make_mesh(4)

    def texts():
        out = []
        for kw in ({}, {"mesh": mesh}):
            lowered = make_train_step(model, HW, NUM_CLASSES, donate_state=False, **kw).lower(state, batch)
            out.append((lowered.as_text(), lowered.as_text(debug_info=True)))
        return out

    scoped = texts()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = texts()
    for (plain_a, debug_a), (plain_b, debug_b) in zip(scoped, bare):
        assert plain_a == plain_b
        assert "stage2" in debug_a and "grad_allreduce" not in plain_a
        assert debug_a != debug_b and "optimizer" not in debug_b


class _Compiles:
    """Compile requests of this process while the block runs, and how many
    of them the persistent cache answered."""

    _EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
               "/jax/compilation_cache/cache_hits": "hits"}

    def __enter__(self):
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_event(self, event, **_kw):
        name = self._EVENTS.get(event)
        if name:
            setattr(self, name, getattr(self, name) + 1)


@pytest.fixture
def _no_ring():
    trace.reset()
    yield
    trace.reset()


@pytest.mark.parametrize("devices", [1, 4])
def test_compiled_step_after_a_run_compiles_nothing_and_keeps_no_buffer(_no_ring, devices):
    model = _model()
    before = {id(x) for x in jax.live_arrays()}  # other tests' fixtures, in this process
    state = create_train_state(model, _optimizer(), (1, *HW, 3), jax.random.key(0))
    largest_leaf = max(x.size for x in jax.tree.leaves(state.params))
    mesh = make_mesh(devices) if devices > 1 else None
    state = loop.run_training(model, state, _host_batches(), NUM_CLASSES,
                              loop.LoopConfig(total_steps=2, log_every=0), mesh=mesh)
    with _Compiles() as c:
        compiled = loop.compiled_step()
        assert loop.compiled_step(HW).as_text() == compiled.as_text()
    # the executable the loop ran, not another compilation of it
    assert (c.requests, c.hits) in ((0, 0), (2, 2)), (c.requests, c.hits)
    table = scope_table(compiled)
    assert {"backbone", "assign", "loss", "optimizer"} <= {s for s, _, _ in table.values()}
    assert ("grad_allreduce" in {s for s, _, _ in table.values()}) == (devices > 1)
    flops = compiled.cost_analysis()
    assert (flops[0] if isinstance(flops, list) else flops)["flops"] > 0
    with pytest.raises(LookupError):
        loop.compiled_step((32, 32))
    # What the loop remembers is abstract: with the returned state gone no
    # parameter-sized or batch-sized buffer of the run is alive.
    step_fn, (abstract_state, abstract_batch) = loop._built_steps[HW]
    assert all(isinstance(x, jax.ShapeDtypeStruct)
               for x in jax.tree.leaves((abstract_state, abstract_batch)))
    assert abstract_batch["images"].shape == (BATCH, *HW, 3)
    del state, compiled
    gc.collect()
    alive = [x for x in jax.live_arrays() if id(x) not in before and x.size >= largest_leaf]
    anchors = [x for x in alive if x.ndim == 2 and x.shape[1] == 4]  # the step's constant
    assert len(alive) == len(anchors) <= 1, [(x.shape, x.dtype) for x in alive]


def test_compiled_step_before_any_run_says_so(_no_ring):
    loop._built_steps.clear()
    with pytest.raises(LookupError, match="has built no train step"):
        loop.compiled_step()


def test_cost_analysis_is_recorded_after_the_first_execution_from_the_cache(_no_ring, tmp_path):
    """With the ring on, the step's FLOPs come from the executable the first
    call compiled or loaded: no lowering before it, and nothing compiled
    for it (the warm start of ``train.py --obs-trace`` loads the step)."""
    model = _model()
    state = create_train_state(model, _optimizer(), (1, *HW, 3), jax.random.key(0))
    # Warm the persistent cache the way an earlier run would have.
    state = loop.run_training(model, state, _host_batches(), NUM_CLASSES,
                              loop.LoopConfig(total_steps=1, log_every=0))
    trace.configure(str(tmp_path), process_label="t")
    with _Compiles() as c:
        loop.run_training(model, state, _host_batches(), NUM_CLASSES,
                          loop.LoopConfig(total_steps=3, log_every=0))
    assert c.requests == c.hits == 1  # the rebuilt step function: loaded, and once
    events = trace.snapshot_events()
    cost = [e for e in events if e["name"] == "cost_analysis"]
    assert len(cost) == 1 and cost[0]["args"]["flops"] > 0
    assert cost[0]["args"] == dict(cost[0]["args"], target="train_step", bucket="64x64", batch=BATCH)
    # The step's first call is the tail of its compile phase (ISSUE 34), no ``step`` span.
    # Phases are kept with the ring off too, so the warming run's build is exported beside this one's.
    built = max((e for e in events if e["name"] == "compile_train_step"), key=lambda e: e["ts"])
    assert cost[0]["ts"] >= built["ts"] + built["dur"]
    assert len([e for e in events if e["name"] == "step"]) == 1  # steps 2 and 3 ran; 2 was the build


def _lm_run():
    from batchai_retinanet_horovod_coco_tpu.data.tokens import PackedTokensConfig, packed_token_batches
    from batchai_retinanet_horovod_coco_tpu.models import granite_hybrid

    model = granite_hybrid.GraniteHybrid(granite_hybrid.TINY)
    tx = make_optimizer(OptimizerConfig(optimizer="adamw", schedule="constant", warmup_steps=0))[0]
    state = create_train_state(model, tx, (1, 8), jax.random.key(0), example_dtype=LMTask.example_dtype)
    batches = packed_token_batches(PackedTokensConfig(vocab_size=128, seq_len=64, batch_size=1, seed=0))
    return model, state, batches, None, LMTask()


def _detection_run():
    model = _model()
    state = create_train_state(model, _optimizer(), (1, *HW, 3), jax.random.key(0))
    return model, state, _host_batches(), NUM_CLASSES, None


@pytest.mark.parametrize("run,more", [
    (_detection_run, {}),
    # 64 tokens in chunks of 8 on the CPU (ops/attention.py::lowering, ops/ssd.py::lowering,
    # ops/document_conv.py::lowering; the CPU states no memory limit: models/lm_layers.py::layer_keeps)
    (_lm_run, {"attention_lowering": "xla", "ssd_lowering": "xla", "conv_lowering": "xla",
               "layer_keeps": "attention_residuals,dsa_threshold", "mlp_gate_up_layers": 0, "mlp_gate_up_bytes": 0}),
])
def test_run_meta_says_which_lowering_the_lm_steps_attention_took(_no_ring, tmp_path, run, more):
    """One ``run_meta`` instant a run, before the step's compile span: the
    devices, and for the language model what its attention layer and its
    mixers' scans lower to and what its recomputed layers keep."""
    model, state, batches, num_classes, task = run()
    trace.configure(str(tmp_path), process_label="t")
    loop.run_training(model, state, batches, num_classes, loop.LoopConfig(total_steps=1, log_every=0), task=task)
    events = trace.snapshot_events()
    (meta,) = [e for e in events if e["name"] == "run_meta"]
    assert meta["args"] == {"device_kind": jax.devices()[0].device_kind, "process_count": 1,
                            "local_device_count": jax.local_device_count(), **more}
    assert meta["ts"] <= min(e["ts"] for e in events if e["name"] == "compile_train_step")


def test_the_sparse_attention_models_step_files_every_operation_it_can_under_a_scope_of_the_model():
    """models/keye_vl2.py through the shared step: the model enters ``attention``
    (with ``indexer``, ``select``, ``attention_core`` and ``indexer_loss`` beneath
    it) and ``moe`` (without ``shared``), and none of the other models' scopes;
    forward, recomputed forward and backward keep the scope; every matrix
    product and every sort of the compiled step lies under one of the model's
    scopes or the optimizer's, none is unscoped."""
    from batchai_retinanet_horovod_coco_tpu.models import keye_vl2
    from batchai_retinanet_horovod_coco_tpu.train.step import UNSCOPED

    model = keye_vl2.KeyeVL2(keye_vl2.TINY)
    tx = make_optimizer(OptimizerConfig(optimizer="adamw", schedule="constant", warmup_steps=0))[0]
    state = create_train_state(model, tx, (1, 8), jax.random.key(0), example_dtype=LMTask.example_dtype)
    seg = jnp.asarray(np.repeat([[0, 1, 2], [0, 1, 1]], [20, 30, 14], axis=1), jnp.int32)
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32), "segment_ids": seg}
    compiled = make_train_step(model, (2, 64), None, task=LMTask(), donate_state=False).lower(state, batch).compile()
    table = scope_table(compiled)
    filed = {(s, d) for s, d, _ in table.values()}
    assert model.scopes == ("embed", "attention", "moe", "lm_head", "loss")
    assert set(model.scopes) <= set(STEP_SCOPES)
    for s in (*model.scopes, "optimizer"):
        assert (s, "fwd") in filed, s
    assert {s for s, d in filed if d == "bwd"} >= set(model.scopes)
    assert not {"mla", "dense_mlp", "mlp", "mamba"} & {s for s, _ in filed}
    assert not set(DetectionTask.scopes) - {"loss"} & {s for s, _ in filed}
    # keye's four names beneath ``attention``; since PR 46 two more, models/afmoe.py's, which this step does not enter
    assert STEP_SCOPES["attention"] == ("indexer", "select", "attention_core", "indexer_loss", "window_core", "full_core")
    for slice_, beneath in (("attention", STEP_SCOPES["attention"][:4]), ("moe", ("router", "dispatch", "experts", "combine", "aux"))):
        paths = {p for t, _, p in table.values() if t == slice_}
        for name in beneath:
            assert any(f"/{name}/" in p or p.endswith("/" + name) for p in paths), (slice_, name)
    assert not any("/shared" in p for t, _, p in table.values() if t == "moe")  # no shared expert
    # the selection has no backward of its own, the indexer and its loss have
    second = lambda p: next((n for n in p.split("/")[1:] if n in STEP_SCOPES["attention"]), "-")
    directions = {}
    for t, d, p in table.values():
        if t == "attention":
            directions.setdefault(second(p), set()).add(d)
    assert directions["indexer"] == directions["attention_core"] == directions["indexer_loss"] == {"fwd", "bwd"}
    assert not {"window_core", "full_core"} & set(directions)
    work = {n: table[n] for n, op in _instructions(compiled).items() if op in ("dot", "sort", "convolution")}
    assert work and not [n for n, (s, _, _) in work.items() if s == UNSCOPED]
    assert {s for s, _, _ in work.values()} >= {"attention", "moe", "lm_head"}
    assert {s for s, _, _ in work.values()} <= {*model.scopes, "optimizer"}
