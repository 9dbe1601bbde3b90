"""``core/scopes.py``: the program remembers what it dispatched and,
only when asked, hands out the optimized HLO of each program, in whose
``op_name``s the reader (``benchmark/scope_time.py``) finds the
``named_scope``s."""

import gc
import re
import weakref

import numpy as np
import pytest

from benchmark import scope_time
from mmlspark_tpu.core import scopes
from tests.dl.test_hybrid_lm import CFG as HYBRID
from tests.dl.test_hybrid_lm import _prompts

PATTERN = scope_time.scope_pattern(("gbdt", "lm"))


@pytest.fixture(autouse=True)
def empty_registry(monkeypatch):
    monkeypatch.setattr(scopes, "_ENTRIES", {})


def _tables():
    """What the reader makes of the registry."""
    return {name: [scope_time.scope_table(text, PATTERN) for text in texts]
            for name, texts in scopes.hlo_texts().items()}


class Counted:
    """A jitted function that counts its lowerings."""

    def __init__(self, jitted):
        self.jitted, self.__name__, self.lowered = jitted, jitted.__name__, 0

    def lower(self, *args):
        self.lowered += 1
        return self.jitted.lower(*args)


def _loops():
    """A scan whose body opens a scope, a ``fori_loop`` inside one, and
    an op under none."""
    import jax
    import jax.numpy as jnp

    def loops(x):
        def body(c, _):
            with jax.named_scope("lm.inner"):
                c = jnp.sin(c) @ c
            return c, None

        x, _ = jax.lax.scan(body, x, None, length=3)
        with jax.named_scope("gbdt.loop"):
            x = jax.lax.fori_loop(0, 4, lambda i, c: jnp.cos(c) * 2, x)
        return jnp.tanh(x)

    return jax.jit(loops)


def test_two_shapes_of_one_program_are_two_entries_under_one_name():
    import jax.numpy as jnp

    program = _loops()
    scopes.register(program, jnp.ones((8, 8)))
    scopes.register(program, jnp.ones((16, 16)))
    scopes.register(program, jnp.ones((16, 16)))      # a repeat: no entry
    assert len(scopes._ENTRIES) == 2
    found = scopes.hlo_texts()
    assert list(found) == ["jit_loops"]
    small, large = found["jit_loops"]
    assert "f32[8,8]" in small and "f32[16,16]" not in small
    assert "f32[16,16]" in large and "f32[8,8]" not in large
    assert small.startswith("HloModule jit_loops")


def test_register_lowers_nothing_and_keeps_shapes_not_arrays():
    import jax.numpy as jnp

    program = Counted(_loops())
    x = jnp.ones((8, 8))
    for _ in range(3):
        scopes.register(program, x)
    assert program.lowered == 0
    scopes.hlo_texts()
    assert program.lowered == 1             # once an entry and call
    ((name, _, (shape,)),) = scopes._ENTRIES.values()
    assert name == "jit_loops"
    assert type(shape).__name__ == "ShapeDtypeStruct"
    assert (shape.shape, shape.dtype) == ((8, 8), np.float32)


def test_a_python_scalar_and_a_none_leaf_are_registered_as_they_are():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(data, it):
        with jax.named_scope("gbdt.grad"):
            return data["x"] * it

    scopes.register(step, {"x": jnp.ones(4), "token": None}, 3)
    scopes.register(step, {"x": jnp.ones(4), "token": None}, 7)
    ((name, _, _),) = scopes._ENTRIES.values()
    assert name == "jit_step"
    assert "gbdt.grad" in _tables()["jit_step"][0].values()


def test_body_instructions_carry_their_scope_and_a_loop_takes_its_bodys():
    import jax.numpy as jnp

    program = _loops()
    x = jnp.ones((64, 64))
    scopes.register(program, x)
    (text,) = scopes.hlo_texts()["jit_loops"]
    table = scope_time.scope_table(text, PATTERN)
    assert set(table.values()) == {"lm.inner", "gbdt.loop", None}
    by_scope = {}
    for name, scope in table.items():
        by_scope.setdefault(scope, []).append(name)
    # the body's own instructions, whatever XLA fused them into
    assert any(n.startswith(("sin", "wrapped_sin")) for n in
               by_scope["lm.inner"])
    assert any("dot" in n for n in by_scope["lm.inner"])
    assert any("cos" in n or "multiply" in n for n in by_scope["gbdt.loop"])
    # the scan's while carries no scope of its own and takes the one its
    # body agrees on; the fori_loop's was opened inside one
    whiles = {n: re.search(r'%s = .*op_name="([^"]*)"' % re.escape(n), text)
              .group(1) for n in table if n.startswith("while")
              and re.search(r"%s = \(.*\) while\(" % re.escape(n), text)}
    assert sorted(table[n] for n in whiles) == ["gbdt.loop", "lm.inner"]
    scan = next(n for n in whiles if table[n] == "lm.inner")
    assert scope_time.scope_of(whiles[scan], PATTERN) is None
    # and the op under no scope has none
    assert any("tanh" in n for n in by_scope[None])
    assert not any("tanh" in n for s in ("lm.inner", "gbdt.loop")
                   for n in by_scope[s])


def test_the_registry_is_bounded_and_drops_the_longest_unused(monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(scopes, "_ENTRY_LIMIT", 3)
    program = _loops()
    for n in (1, 2, 3):
        scopes.register(program, jnp.ones((n, n)))
    scopes.register(program, jnp.ones((1, 1)))      # used again
    scopes.register(program, jnp.ones((4, 4)))
    assert [shapes[0].shape[0] for _, _, shapes in scopes._ENTRIES.values()] \
        == [3, 1, 4]


# -- through the stage and the fit --------------------------------------

# test_hybrid_lm's tiny model at three layers: delta rule over the dense
# SwiGLU, latent attention and delta rule over the experts
LM = dict(HYBRID, num_hidden_layers=3)
LM_SCOPES = {"lm.embed", "lm.gdn", "lm.mla", "lm.mla.write", "lm.mlp",
             "lm.moe", "lm.moe.route", "lm.moe.dispatch", "lm.moe.experts",
             "lm.moe.combine", "lm.moe.shared", "lm.last"}


def _compiles():
    """A list that grows by one at every backend compile or read from
    the persistent cache (``jax.monitoring``)."""
    import jax.monitoring

    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: seen.append(event)
        if event.endswith("backend_compile_duration") else None)
    return seen


def test_a_dropped_stage_frees_its_weights_and_its_tables_still_answer(
        monkeypatch):
    """Eight ragged rows, a prefill step in 4 groups of 2: the two
    programs are registered a device batch, nothing is lowered or
    compiled for it, and after the stage is gone the registry (which
    holds the module and numbers, no array) still lowers both, and the
    reader finds every scope in their texts."""
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.dl.backbones import HybridLM, lm_init_params
    from mmlspark_tpu.dl.causal_lm import CausalLM

    monkeypatch.setattr(HybridLM, "GROUP_TOKENS", 16)
    stage = CausalLM(inputCol="prompt", outputCol="completion",
                     modelConfig=LM, maxNewTokens=4, batchSize=8,
                     prefillChunk=8, maxLength=64).set_weights(
                         lm_init_params(LM, 3))
    frame = DataFrame({"prompt": _prompts([3, 5, 9, 12, 17, 20, 30, 40])})
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(scopes, "hlo_texts", lambda: 1 / 0)
        stage.transform(frame)
        seen = _compiles()
        stage.transform(frame)              # warm: registers, compiles nothing
    assert seen == []
    assert sorted(name for name, _, _ in scopes._ENTRIES.values()) \
        == ["jit_lm_generate", "jit_lm_prefill"]
    leaf = weakref.ref(stage._scorer._params["params"]["embedding"])
    assert leaf() is not None
    del stage
    gc.collect()
    assert leaf() is None

    found = _tables()
    assert sorted(found) == ["jit_lm_generate", "jit_lm_prefill"]
    (prefill,), (generate,) = found["jit_lm_prefill"], found["jit_lm_generate"]
    assert set(prefill.values()) >= LM_SCOPES | {"lm.group"}
    assert set(generate.values()) >= LM_SCOPES - {"lm.last"} | {
        "lm.sample", "lm.head"}
    assert "lm.group" not in generate.values()   # a decode step is one group


def test_the_new_scopes_stand_in_the_tiny_hybrid_models_lowered_text(
        monkeypatch):
    """``lm.group`` (the groups' cuts and pastes), ``lm.sample`` (the
    greedy choice and its log-probability) and ``lm.last`` (the last
    real token's hidden row) are in the two programs' locations, as
    ``gbdt.*`` is in the step's."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.dl.backbones import (HybridLM, lm_init_state, lm_module,
                                           lm_param_shapes)
    from mmlspark_tpu.dl.causal_lm import (lm_generate_program,
                                           lm_prefill_program)

    monkeypatch.setattr(HybridLM, "GROUP_TOKENS", 16)
    module, shapes = lm_module(LM), lm_param_shapes(LM)
    prefill = jax.jit(lm_prefill_program(module, 8, 4)).lower(
        shapes, jnp.zeros((8, 32), jnp.int32), jnp.zeros((8,), jnp.int32))
    generate = jax.jit(lm_generate_program(module, 4, False)).lower(
        shapes, jnp.zeros((8, 64)), lm_init_state(LM, 8, 36))
    in_prefill = set(re.findall(r"lm\.[a-z_.]+[a-z]",
                                prefill.as_text(debug_info=True)))
    in_generate = set(re.findall(r"lm\.[a-z_.]+[a-z]",
                                 generate.as_text(debug_info=True)))
    assert in_prefill >= LM_SCOPES | {"lm.group"}
    assert in_generate >= LM_SCOPES | {"lm.sample", "lm.head"}
    assert "lm.sample" not in in_prefill and "lm.group" not in in_generate
    assert "lm." not in prefill.as_text()       # metadata: not in the program


def test_a_fit_registers_its_step_once_and_lowers_nothing_for_it():
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.gbdt.estimators import LightGBMClassifier

    rng = np.random.default_rng(0)
    x = rng.normal(size=(400, 4)).astype(np.float32)
    df = DataFrame({"features": x, "label": (x[:, 0] > 0).astype(np.float64)})

    def fit():
        return LightGBMClassifier(numIterations=3, numLeaves=7, maxDepth=3,
                                  minDataInLeaf=5).fit(df)

    fit()
    ((name, _, _),) = scopes._ENTRIES.values()
    assert name == "jit_step"
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(scopes, "hlo_texts", lambda: 1 / 0)
        seen = _compiles()
        fit()                               # the same step: the same entry
    assert len(scopes._ENTRIES) == 1
    assert seen == []
    (table,) = _tables()["jit_step"]
    assert set(table.values()) >= {"gbdt.grad", "gbdt.hist", "gbdt.split",
                                   "gbdt.leaf", "gbdt.route", "gbdt.predict",
                                   "gbdt.metric"}
