"""The contract every served family's module holds its model to, written
once: the helpers, the fixtures and the tests that recur in
tests/test_{qwen3_next,granite_moe_hybrid,nemotron_h,olmo_hybrid,lfm2_moe,
mellum2,pangu_ultra_moe,olmoe}.py.

A family's module defines ONE record at its top, `FAMILY = Family(...)`
(what differs between the families: the tiny published keys, the engine's
configuration, the reference, the tolerance and the reason for it, the
controls' distance, the cut's file), and imports by name the fixtures and
the cases it takes:

    from _family import (  # noqa: F401 - collected here
        engines, family, model, pytest_generate_tests, served,
        test_a_chunk_boundary_at_every_offset, ...)

An imported case is collected under the importing module, so a family's
failure names the family and `--dist loadfile` still gives every family
a worker. A case's parameters (`chunk`, `control`, ...) are its family's:
`pytest_generate_tests` below reads them from the record. The module
keeps what is its own: its operator's oracle tests, its refusals, its
numbers.

Engines: `init_inference` makes new jitted closures, so nothing compiled
for one engine serves the next, and a family's module is mostly XLA
compiling. `engines` (module-scoped) builds ONE engine for each distinct
(overrides, kernel lane) a module asks for and hands it to every test
that asks for the same. Sharing is sound because of what the tests
already assert: `serve` makes a new scheduler (the counters are the
scheduler's), a run ends with nothing tracked, and the teacher-forced
tests flush what they put. `engines` checks that on hand-out and fails
the test that finds an engine dirty, naming the test that left it so. A
test that must own its engine (it patches the module under the build,
reads the build's log or spans, expects the build to raise, serves
another model, or changes the engine) takes `engines.fresh(...)`, the one
way to one, and says why in one line.

What the tests cost is compiling (README.md "A served family's tests"
has the rules and the budget, a target of 150 case-seconds a module): one
engine a distinct configuration; each side of a comparison ONE program
(`jax.jit(fn)(...)`: jnp called bare is a small compile an op a shape);
and ONE width for the reference, which runs op by op on purpose (`feed`
and `greedy_by_the_reference` pad every prompt to [2, 128]).
"""

import dataclasses
import json
import os
import pathlib
import types
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.tests import helpers
from deepspeed_tpu.inference import (
    ServingScheduler,
    ServingSchedulerConfig,
    init_inference,
)
from deepspeed_tpu.inference import engine as E
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops import pallas
from deepspeed_tpu.ops.pallas import paged_attention as PA
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmarks"
# another architecture's config, for keys that must stay an error there
MISTRAL = {"architectures": ["MistralForCausalLM"], "hidden_size": 64,
           "intermediate_size": 128, "num_attention_heads": 4,
           "num_key_value_heads": 2, "num_hidden_layers": 2,
           "vocab_size": 64}


@dataclasses.dataclass(frozen=True)
class Family:
    """What differs between the served families' modules."""
    hf: dict                    # the tiny configuration, published keys
    ref: types.ModuleType       # benchmarks/reference/<family>.py
    atol: float                 # engine against reference; the module says why
    engine: dict                # the teacher-forced tests' engine
    # (a leaf's name, 4 x init's value, a key) -> the value the tests
    # use: what makes every scale, tap, decay and bias matter
    jig: Optional[Callable] = None
    spread: float = 0.0         # the reference's largest logit exceeds it
    far: float = 30             # every control differs by over far x atol
    chunks: tuple = (1, 2, 3, 4, 7)   # first chunks before the prompt's end
    training_refuses: str = "layer_types"  # what the training forward names
    run_tokens: Optional[str] = None  # the scheduler's counter of rows in runs
    # where kernels run: whether an 8-row step takes the matrix state's
    # step kernel and the convolution's one pass, a scope its program holds
    with_kernels: tuple = (True, True, None)
    unservable: tuple = ()      # (what, hf, match): config_from_hf refuses
    cut: Optional[pathlib.Path] = None    # the cell's configuration
    reduced: tuple = ()         # the keys the cut reduces
    assumed: tuple = ()         # the keys the cut's `assumed` explains
    held: Optional[dict] = None  # the cut's experts_held, of a share


# -- helpers ---------------------------------------------------------------

def top(params):
    return {k: v for k, v in params.items() if k != "layers"}


def layer_fn(params):
    return lambda l: jax.tree.map(lambda a: a[l], params["layers"])


def ref_logits(family, params, toks, mutate=None, hf=None):
    return np.asarray(family.ref.forward_logits(
        top(params), layer_fn(params), toks, hf or family.hf, mutate))


def block(params, n_tokens):
    """(the second layer's weights, seeded rows of d 64): what a routed
    block alone is run on."""
    lw = jax.tree.map(lambda a: a[1], params["layers"])
    h = jnp.asarray(np.random.default_rng(3).normal(size=(n_tokens, 64)),
                    jnp.float32)
    return lw, h


def expert_stacks(X, d, f):
    """The abstract bf16 stacks of X gated experts of d x f: what
    model.expert_path reads of a layer."""
    stack = jax.ShapeDtypeStruct((X, d, f), jnp.bfloat16)
    return {"w_gate": stack, "w_in": stack,
            "w_out": jax.ShapeDtypeStruct((X, f, d), jnp.bfloat16)}


def shares_of_two_add_up(family, experts_key, n, lw, full, whole, alike):
    """Guide section 4: the routed parts that the shares of two experts
    give (share i holds experts 2i and 2i + 1 of `full`'s stacks, told
    so by `experts_held`, through the PROGRAM's expert layer), with what
    every chip computes alike (the shared expert) counted ONCE, add up
    to what the uncut reference gives for the whole layer."""
    of = next(iter(full.values())).shape[0]
    parts = []
    with jax.default_matmul_precision("highest"):
        for start in range(0, of, 2):
            cfg = config_from_hf(dict(
                family.hf, **{experts_key: 2}, experts_held={"start": start},
                reduced={experts_key: {"published": of, "here": 2}}))
            assert cfg.experts_held == (start, 2)
            lp = dict(lw, **{k: w[start:start + 2] for k, w in full.items()})
            parts.append(M._mlp(n, lp, cfg) - alike)
    np.testing.assert_allclose(sum(parts) + alike, whole, atol=2e-5)
    assert float(jnp.abs(whole - alike).max()) > 0.01  # the routed part counts


def float8(x):
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def cut_of(family):
    """(the cut's file, the configuration it builds)."""
    hf = json.loads(family.cut.read_text())
    return hf, config_from_hf(hf, **hf["serve"]["model_overrides"])


def one_stack(cfg, tree):
    """init's tree is ONE homogeneous stack and top-level ARRAYS (what
    the benchmark's weight maker and reference_inputs take): every leaf
    by its name, stacked and top-level together."""
    assert all(not isinstance(v, dict) for k, v in tree.items()
               if k != "layers")
    assert all(v.shape[0] == cfg.n_layers for v in tree["layers"].values())
    return dict(tree["layers"], **top(tree))


def feed(family, model, eng, full, cuts, hf=None):
    """Teacher-forced put() logits of the prompts `full`, prompt i fed up
    to cuts[i][0], then to cuts[i][1], ...: (engine logits [prompts,
    feeds, V], the reference's at the same positions, the tokens padded
    to one length, the cuts). Flushes what it put."""
    uids = list(range(100, 100 + len(full)))
    got = []
    for j in range(len(cuts[0])):
        toks = [f[(c[j - 1] if j else 0):c[j]] for f, c in zip(full, cuts)]
        got.append(np.asarray(eng.put(uids, toks)))
    for u in uids:
        eng.flush(u)
    # ONE width for every call of a module: the reference runs op by op,
    # and another shape is another ~140 small compiles; what follows a
    # prompt's end cannot reach the positions read (every operator is
    # causal). Seeded tokens, not one token over and over: a control's
    # recurrence (`no_l2norm`) must stay finite over the padding too
    padded = np.random.default_rng(len(full[0])).integers(
        0, family.hf["vocab_size"], (len(full), max(128, *map(len, full)))
    ).astype(np.int32)
    for i, f in enumerate(full):
        padded[i, :len(f)] = f
    want = ref_logits(family, model[1], padded, hf=hf)
    want = np.stack([want[i, np.asarray(c) - 1] for i, c in enumerate(cuts)])
    return np.stack(got, axis=1), want, padded, cuts


def feeds(family, model, eng, lens, splits, n_dec, seed=0, hf=None):
    """`feed` of seeded prompts of `lens`, each fed as len - sum(splits)
    tokens whole, then chunks of `splits`, then n_dec single tokens."""
    rng = np.random.default_rng(seed)
    full = [rng.integers(0, family.hf["vocab_size"], n + n_dec
                         ).astype(np.int32) for n in lens]
    cuts = [[n - sum(splits[j:]) for j in range(len(splits) + 1)]
            + [n + j + 1 for j in range(n_dec)] for n in lens]
    return feed(family, model, eng, full, cuts, hf=hf)


def requests(family, n, seed=5):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, family.hf["vocab_size"], int(rng.integers(9, 60))
                          ).tolist(), int(rng.integers(3, 12)))
            for _ in range(n)]


def serve(eng, requests, **sched):
    """A NEW scheduler over `eng` (its counters start at zero), run to
    the end: (the scheduler, every request's tokens)."""
    s = ServingScheduler(eng, ServingSchedulerConfig(
        **dict(dict(max_num_batched_tokens=48, prefill_chunk=8,
                    prefill_mode="chunked", decode_chunk=1, warmup=False),
               **sched)))
    rids = [s.submit(p, max_new_tokens=n) for p, n in requests]
    s.run()
    return s, [s.finished[r].output for r in rids]


def greedy_by_the_reference(family, model, requests, outputs):
    """Every served token is the reference's argmax at its position,
    teacher-forced on the served tokens themselves (to a margin: two
    logits closer than the tolerance may swap)."""
    for (prompt, _), out in zip(requests, outputs):
        # one shape for every request, and `feed`'s: padding after the
        # end cannot reach the positions read under a causal mask, and
        # the reference compiles its small programs once a module
        toks = np.zeros((2, 128), np.int32)
        toks[0, :len(prompt) + len(out)] = prompt + out
        logits = ref_logits(family, model[1], toks)[0]
        for j, t in enumerate(out):
            row = logits[len(prompt) + j - 1]
            assert row[t] >= row.max() - family.atol, (j, t, row.argmax())


def through_reused_slots(family, model, eng):
    """Twice as many requests of unequal lengths as `eng` has slots (12
    through 6, a row budget of 6 sequences at a time): every slot is
    handed on to a later sequence, and what the last one left in it
    (here: NaN, put there before the first admission too, in every pool
    of state) never reaches the next: (the scheduler's counters, the
    requests). `eng` is the module's shared one on purpose: every later
    test on it is a second witness (the pad rows' slot stays NaN)."""
    eng.cache = eng.cache._replace(state=jax.tree.map(
        lambda p: jnp.full_like(p, jnp.nan), eng.cache.state))
    slots = family.engine["max_tracked_sequences"]
    asked = requests(family, 2 * slots)
    s, outputs = serve(eng, asked)
    assert all(len(o) == n for o, (_, n) in zip(outputs, asked))
    greedy_by_the_reference(family, model, asked, outputs)
    d = s.counters
    assert d["state_slot_resets"] == 2 * slots
    assert d["state_slots_live"] >= d["steps"] > 0
    assert eng.state.n_tracked == 0 and len(eng.state._free_slots) == slots
    assert d["lookahead_steps"] > 0  # the slot is updated in program order
    return d, asked


def step_text(eng):
    """The 8-row shared-table decode step as the engine lowers it, with
    its scopes."""
    return eng._decode_fn(8, False).lower(
        eng.params, eng.cache, *(eng._dev(np.zeros(s, np.int32)) for s in
                                 ((8,), (8, eng.config.blocks_per_seq), (8,))),
        *eng.state_args(np.zeros((8,), np.int32))).as_text(debug_info=True)


def kernels(text):
    """The Mosaic kernels of a program compiled for the TPU."""
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def on_chip(one_chip, dtype):
    """sds(shape, dtype=dtype): a ShapeDtypeStruct on the described chip."""
    return lambda shape, dtype=dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def compiles_one_aliased_kernel(step, args, pool_arg, name):
    """`step` compiled for the chip `args` are on, its pool (args[
    pool_arg]) donated: ONE Mosaic kernel, named `name`, the pool
    aliased in and out (no second pool among the temporaries)."""
    compiled = jax.jit(step, donate_argnums=(pool_arg,)).lower(
        *args).compile()
    calls = kernels(compiled.as_text())
    assert len(calls) == 1 and name in calls[0]
    mem = compiled.memory_analysis()
    pool = args[pool_arg]
    assert mem.alias_size_in_bytes >= pool.size * pool.dtype.itemsize
    assert mem.temp_size_in_bytes < 64 << 20


def walk_and_write_compile(one_chip, rows, heads, kv_heads, d, pool_shape):
    """The row write and the live-block walk over bfloat16 pools of
    `pool_shape`, a table of 32 slots a row, compiled for the chip: both
    kernels are in the program."""
    sds = on_chip(one_chip, jnp.bfloat16)
    pool, q = sds(pool_shape), sds((rows, heads, d))
    new = sds((rows, kv_heads, d))
    table, ints = sds((rows, 32), jnp.int32), sds((rows,), jnp.int32)

    def fn(q, kc, vc, kn, vn, table, ctx, slots):
        kc, vc = PA.paged_kv_write(kc, vc, kn, vn, slots)
        return PA.paged_decode_attention(q, kc, vc, table, ctx), kc, vc

    text = jax.jit(fn, donate_argnums=(1, 2)).lower(
        q, pool, pool, new, new, table, ints, ints).compile().as_text()
    for name in ("paged_decode_grid", "paged_kv_write"):
        assert any(name in line for line in kernels(text)), name


def walk_matches_the_oracle(rng, H, KV, D):
    """The (interpreted) walk of five rows of H query / KV heads of D
    over blocks of 16 against its XLA oracle: contexts that end inside a
    block, at a block's edge and nowhere (a pad row)."""
    S, bs, NB = 5, 16, 4
    ctx = np.asarray([1, 17, 40, 64, 0], np.int32)
    NBLK = S * NB + 1
    q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(NBLK - 1)[:S * NB].reshape(S, NB),
                      jnp.int32)
    # each side ONE program (op by op the oracle is thirty small compiles)
    got, want = (jax.jit(fn)(q, kc, vc, tbl, jnp.asarray(ctx)) for fn in (
        PA.paged_decode_attention, PA.paged_decode_attention_xla))
    np.testing.assert_allclose(got[:4], want[:4], atol=2e-5)


# -- the engines of a module -----------------------------------------------

def _free(eng):
    s = eng.state
    return s.n_tracked, len(s._free_slots), s.free_blocks, s.free_rings


def _this_test():
    # pytest's own: "tests/test_x.py::test_y[case] (call)"
    return os.environ.get("PYTEST_CURRENT_TEST", "?").rsplit(" ", 1)[0]


class Engines:
    """`engines(**over)`: the module's ONE engine of the family's
    configuration with `over`, in the kernel lane the caller is in (an
    engine built under `pallas_interpret` resolves other kernels, and a
    program keeps the lane it was traced in)."""

    def __init__(self, model, base):
        self.model, self.base = model, base
        self._built = {}  # key -> [engine, what it had free new, last user]

    def fresh(self, model=None, init=None, **over):
        """An engine of the caller's own, the ONE way to one: for a test
        that serves another `model`, patches what the build reads, reads
        what the build logged, expects it to raise (`init`: keywords of
        init_inference itself) or changes the engine. The caller says
        why."""
        mcfg, params = model or self.model
        return init_inference(params, mcfg, dict(self.base, **over),
                              dtype=jnp.float32, **(init or {}))

    def __call__(self, **over):
        key = (repr(sorted(over.items())), pallas.interpret())
        if key not in self._built:
            eng = self.fresh(**over)
            self._built[key] = [eng, _free(eng), None]
        eng, new, last = self._built[key]
        if _free(eng) != new:
            del self._built[key]  # the next test builds its own again
            pytest.fail(f"{last} left the shared engine {key} dirty: "
                        f"(tracked, free slots, blocks, rings) = "
                        f"{_free(eng)}, new it had {new}")
        self._built[key][2] = _this_test()
        return eng

    def sched(self, **over):
        """An engine whose row budget admits as many sequences as it has
        slots (the scheduler admits up to max_batch_size, and the
        tracked-sequence cap is an error, not a wait:
        tests/test_overload.py)."""
        return self(max_batch_size=self.base["max_tracked_sequences"], **over)


# -- fixtures (module-scoped: a worker holds one module's engines) ----------

@pytest.fixture(scope="module")
def family(request):
    return request.module.FAMILY


def model_of(family, hf=None):
    """(the tiny configuration `hf`, the family's own by default, seeded
    float32 weights): init's values times 4 (the 0.02 init gives nearly
    flat logits), each leaf then as the family's `jig` says."""
    mcfg = config_from_hf(hf or family.hf, use_flash=False)

    def jigged(tree, salt):
        return {k: family.jig(k, v, jax.random.fold_in(
            jax.random.PRNGKey(salt), i)) for i, (k, v) in
            enumerate(tree.items())}

    def make():
        params = jax.tree.map(lambda x: x * 4,
                              T.init(mcfg, jax.random.PRNGKey(1)))
        return dict(jigged(top(params), 2),
                    layers=jigged(params["layers"], 3))

    # ONE program: leaf by leaf the tree is a hundred small compiles
    return mcfg, jax.jit(make)()


@pytest.fixture(scope="module")
def model(family):
    return model_of(family)


@pytest.fixture(scope="module")
def engines(family, model):
    return Engines(model, family.engine)


@pytest.fixture(scope="module")
def served(family, model, engines):
    """Two prompts through whole-prompt prefill, a chunk of five and six
    single steps."""
    return feeds(family, model, engines(), [70, 83], [5], 6)


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip to compile for, with no chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


# -- the cases ---------------------------------------------------------------

def _of_the_family(argnames, values):
    """Parametrise a case by its family's record."""
    def mark(test):
        test.of_the_family = (argnames, values)
        return test
    return mark


def pytest_generate_tests(metafunc):
    how = getattr(metafunc.function, "of_the_family", None)
    if how is not None:
        argnames, values = how
        metafunc.parametrize(argnames, values(metafunc.module.FAMILY))


def test_the_cuts_file_keeps_the_published_widths(family):
    hf = json.loads(family.cut.read_text())
    helpers.check_published_widths(hf, BENCH)
    assert sorted(hf["reduced"]) == sorted(family.reduced)
    for key in family.assumed:
        assert hf["assumed"][key]
    if family.held is None:
        assert "share_of" not in hf  # every expert, head and row is here
    else:
        assert hf["share_of"] and hf["stands_for"]
        assert hf["experts_held"] == family.held


@_of_the_family("what,hf,match", lambda f: f.unservable)
def test_what_the_mapping_cannot_serve_is_an_error(what, hf, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(hf)


def test_the_training_forward_refuses_the_family(family, model):
    mcfg, params = model
    with pytest.raises(NotImplementedError, match=family.training_refuses):
        T.forward_hidden(params, jnp.zeros((1, 8), jnp.int32), mcfg)


def test_prefill_chunks_and_single_steps_match_the_reference(family, served):
    got, want, _, _ = served
    assert np.isfinite(got).all()
    # the logits are not flat
    assert np.abs(want).max() > family.spread, np.abs(want).max()
    assert np.abs(got - want).max() < family.atol, np.abs(got - want).max(-1)


@_of_the_family("chunk", lambda f: f.chunks)
def test_a_chunk_boundary_at_every_offset(family, model, engines, chunk):
    """The first chunk starts `chunk` tokens before the prompt's end
    (the family's `chunks`: a run of one, runs shorter and longer than
    what a slot carries of the convolution's inputs, every residue of
    its taps), a second chunk of 4 follows (its first rows read what the
    first left in the slot), then single steps, two prompts of unequal
    lengths side by side (their whole parts in `served`'s prefill
    bucket: the offsets are the decode rows', and a prefill program of
    another width is ten seconds of compiling)."""
    got, want, _, _ = feeds(family, model, engines(), [80, 95], [chunk, 4],
                            3, seed=chunk)
    assert np.abs(got - want).max() < family.atol, np.abs(got - want).max(-1)


@_of_the_family("control",
                lambda f: f.ref.MUTANTS + ("float8_weights",))
def test_a_wrong_model_fails_the_written_tolerance(family, model, served,
                                                   control):
    """Each of the logits audit's controls, put in the reference's
    place: the engine must NOT agree with it. The judge of record for
    whatever the chip's bf16 engine cannot tell from its own rounding
    (`state_bf16`; the traffic files name the others)."""
    got, _, padded, cuts = served
    params = model[1]
    if control == "float8_weights":
        wrong = ref_logits(family, jax.jit(lambda p: jax.tree.map(float8, p))(
            params), padded)
    else:
        wrong = ref_logits(family, params, padded, control)
    wrong = np.stack([wrong[i, np.asarray(c) - 1] for i, c in enumerate(cuts)])
    assert np.abs(got - wrong).max() > family.far * family.atol, control


@pytest.mark.usefixtures("pallas_interpret")
def test_the_engine_with_kernels_matches_the_reference(family, model, engines):
    """decode_impl 'auto' under the interpreter resolves the kernels:
    the walk and the write in the attention layers and, by the family's
    `with_kernels`, the step kernel on the aliased pool and the
    convolution's one pass (channels that are no whole lanes stay
    XLA's)."""
    eng = engines()
    step, carry, scope = family.with_kernels
    assert eng.resolved_impl == "pallas"
    assert (eng.step_kernel(8), eng.carry_kernel(8)) == (step, carry)
    assert scope is None or scope in step_text(eng)
    # (ONE interpreted program of each kind: whole parts of 28 and 32
    # tokens, a chunk of 2 x 4 rows as a scheduler's 8-row step, steps)
    got, want, _, _ = feeds(family, model, eng, [32, 36], [4], 3, seed=4)
    assert np.abs(got - want).max() < family.atol, np.abs(got - want).max(-1)


def test_whole_prompt_waves_and_fused_decode_carry_the_state(family, model,
                                                             engines):
    """prefill_mode 'wave' runs the whole-prompt form (the chunked scan
    of a matrix state) and writes the slot at the prompt's end;
    decode_chunk 4 carries it through a fused scan."""
    asked = requests(family, 6, seed=3)
    s, outputs = serve(engines.sched(), asked, prefill_mode="wave",
                       decode_chunk=4)
    greedy_by_the_reference(family, model, asked, outputs)
    if family.run_tokens:
        assert s.counters[family.run_tokens] == sum(len(p) for p, _ in asked)


def test_preemption_recomputes_to_identical_tokens(family, model, engines):
    """A pool too small for the batch: the youngest sequence is flushed
    and recomputed from its first token in whatever slot it is given."""
    asked = [(p, 40) for p, _ in requests(family, 6, seed=7)]
    _, roomy = serve(engines.sched(), asked)
    s, tight = serve(engines.sched(num_kv_blocks=7), asked)
    assert s.counters["preemptions"] > 0
    assert s.counters["state_slot_resets"] == 6 + s.counters["preemptions"]
    assert tight == roomy


@pytest.mark.parametrize("what,kwargs,config", [
    ("int8_kv", {}, {"kv_cache_dtype": "int8"}),
    ("mesh", {}, {"tp_size": 2}),
    ("weight_quantization", {"quantization": {"bits": 8}}, {}),
    ("offload", {"offload": {"device": "cpu"}}, {}),
])
def test_the_engine_refuses_at_build(model, engines, what, kwargs, config):
    """What a model that holds state beside K/V cannot do right yet is
    refused where it is built (the build raises before it compiles
    anything: no engine to share)."""
    assert E.pool_kinds(model[0]) == ("kv", "state")
    with pytest.raises(NotImplementedError, match=what):
        engines.fresh(init=kwargs, **config)


def test_prefix_credit_and_speculation_are_refused(model, engines):
    assert not E.pools_can(model[0], "prefix_credit")
    with pytest.raises(NotImplementedError, match="speculation"):
        ServingScheduler(engines(), ServingSchedulerConfig(warmup=False),
                         speculative={"ngram": 2, "draft_len": 3})
