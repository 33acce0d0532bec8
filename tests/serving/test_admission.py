"""An admission's host side: the sampling key made on the host
(``_host_key``, bit for bit ``jax.random.PRNGKey``), the programs it queues
back to back and the one read it makes (counted from a CPU profiler trace
and a wrapper around the ways a device value reaches the host), and the
token streams it starts, against a one-request reference keyed on
``jax.random.PRNGKey(seed)`` itself."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import array as array_mod

from elephas_tpu.models.transformer import TransformerLM, select_slot_tokens
from elephas_tpu.serving import ServingEngine
from elephas_tpu.serving import engine as engine_mod

pytestmark = pytest.mark.serving

V = 17
SEEDS = [0, 1, 2**31 - 1, 2**31, 2**32 - 1, 2**32, 2**40 + 5, -1, -2**31,
         -2**40]


def _model():
    return TransformerLM(vocab=V, d_model=16, n_heads=4, n_layers=2,
                         d_ff=32, max_len=48)


def _params(model):
    return {k: jnp.asarray(v) for k, v in model.init(seed=1).items()}


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, V, size=(n,)).astype(
        np.int32)


# -- the key ---------------------------------------------------------------

@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
@pytest.mark.parametrize("seed", SEEDS)
def test_host_key_is_prngkey_bit_for_bit(seed, x64):
    with jax.enable_x64(x64):
        want = np.asarray(jax.random.PRNGKey(seed), np.uint32)
        got = engine_mod._host_key(seed)
    assert got.dtype == np.uint32 and got.shape == (2,)
    assert got.tobytes() == want.tobytes()


def test_host_key_follows_the_seed_offset():
    before = jax.config.jax_random_seed_offset
    jax.config.update("jax_random_seed_offset", 1000)
    try:
        for seed in (0, -1, 2**31 - 1, 2**40 + 5):
            assert engine_mod._host_key(seed).tobytes() == np.asarray(
                jax.random.PRNGKey(seed), np.uint32).tobytes()
    finally:
        jax.config.update("jax_random_seed_offset", before)


# -- what an admission enqueues and reads ----------------------------------

class _Numpy:
    """``numpy`` as the engine module sees it, counting the arrays it is
    asked to copy off the device (on the CPU ``np.asarray`` takes a jax
    array's buffer without ``ArrayImpl._value``)."""

    def __init__(self, reads):
        self._reads = reads

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        if isinstance(a, jax.Array):
            self._reads.append(("np.asarray", a.shape))
        return np.asarray(a, *args, **kw)

    def array(self, a, *args, **kw):
        if isinstance(a, jax.Array):
            self._reads.append(("np.array", a.shape))
        return np.array(a, *args, **kw)


@pytest.fixture
def reads(monkeypatch):
    """Every device-to-host read the engine makes: ``int()``, ``float()``,
    ``bool()``, ``.item()`` and ``.tolist()`` go through
    ``ArrayImpl._value``, ``np.asarray`` / ``np.array`` through the engine
    module's ``np``."""
    out = []
    value = array_mod.ArrayImpl._value

    def counted(self):
        out.append(("_value", self.shape))
        return value.fget(self)

    monkeypatch.setattr(array_mod.ArrayImpl, "_value", property(counted))
    monkeypatch.setattr(engine_mod, "np", _Numpy(out))
    return out


def _executions(trace_dir, lo_name="elephas.engine.prefill"):
    """``(programs, executions)`` the host started inside the one
    ``lo_name`` span of a CPU profiler trace: the jitted functions called
    (``PjitFunction(<name>)``, one name a call) and the executables run
    (``PjRtCpuExecutable::Execute``), eager primitives and library jits
    included."""
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    events = [(e.start_ns, e.duration_ns, e.name)
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines if line.name == "python"
              for e in line.events]
    (lo, dur), = [(s, d) for s, d, n in events if n == lo_name]
    inside = sorted(e for e in events if lo <= e[0] <= lo + dur)
    calls = []
    for s, d, n in inside:
        # a call shows as two nested events of one name
        if n.startswith("PjitFunction(") and not any(
                n == m and t <= s <= t + u for t, u, m in calls):
            calls.append((s, d, n))
    names = [n[len("PjitFunction("):-1] for _, _, n in calls]
    runs = sum(1 for _, _, n in inside if n == "PjRtCpuExecutable::Execute")
    return names, runs


def test_an_admission_queues_three_programs_and_reads_once(tmp_path, reads):
    eng = ServingEngine(_model(), _params(_model()), n_slots=2)
    eng.submit(_prompt(5), 2, temperature=0.8, seed=3)
    eng.drain(max_steps=20)                  # compile outside the trace
    launched = eng.snapshot()["work"]["programs_launched"]
    del reads[:]
    eng.submit(_prompt(7, 1), 3, temperature=0.8, seed=2**40 + 5)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert eng.step() == "prefill"
    finally:
        jax.profiler.stop_trace()
    assert reads == [("_value", ())]         # the first token, once
    names, runs = _executions(str(tmp_path))
    assert names == ["_insert_kernel", "_select_first", "_scatter_row"]
    assert runs == 3                         # and nothing eager between
    assert eng.snapshot()["work"]["programs_launched"] == launched + 3


# -- the streams -----------------------------------------------------------

def _reference(model, params, prompt, n, temperature, seed):
    """One request alone: the model's own cached prefill and decode steps
    at batch 1, each token selected by ``select_slot_tokens`` at the
    position it occupies with ``jax.random.PRNGKey(seed)``."""
    key = jax.random.PRNGKey(seed)[None]
    temps = jnp.asarray([temperature], jnp.float32)
    step = jax.jit(model.decode_step)
    logits, cache = jax.jit(model.prefill)(
        params, jnp.asarray(prompt)[None], model.init_cache(1))
    last, pos, out = logits[:, -1], len(prompt), []
    while len(out) < n:
        tok = select_slot_tokens(last, jnp.asarray([pos]), temps, key)
        out.append(int(tok[0]))
        last, cache = step(params, tok, pos, cache)
        pos += 1
    return out


# (prompt length, tokens, temperature, seed) per request, and the engine's
# options
CASES = {
    "greedy": ([(5, 6, 0.0, 0), (9, 4, 0.0, 11)], {"n_slots": 2}),
    "sampled": ([(6, 8, 0.8, 2**40 + 5), (4, 5, 1.3, -1)],
                {"n_slots": 2}),
    "chunk_train_last_chunk": ([(11, 5, 0.8, 7), (3, 4, 0.0, 2)],
                               {"n_slots": 2, "prefill_chunk": 4}),
    "park_after_one_token": ([(5, 1, 0.8, 4), (7, 1, 0.0, 5),
                              (6, 5, 0.8, 6)], {"n_slots": 1}),
    "resumed": ([(21, 12, 0.8, 0), (19, 12, 0.8, 1), (23, 12, 0.8, 2),
                 (17, 12, 0.8, 3)],
                {"n_slots": 4, "paged": True, "page_size": 8,
                 "pages_per_partition": 12, "prefix_cache": False}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_seeded_streams_equal_the_one_request_reference(case):
    model = _model()
    params = _params(model)
    reqs, opts = CASES[case]
    eng = ServingEngine(model, params, **opts)
    ids, prompts = [], []
    for i, (n, m, temp, seed) in enumerate(reqs):
        prompts.append(_prompt(n, i))
        ids.append(eng.submit(prompts[-1], m, temperature=temp, seed=seed))
    fin = eng.drain(max_steps=5000)
    for rid, p, (_, m, temp, seed) in zip(ids, prompts, reqs):
        assert fin[rid].tokens == _reference(model, params, p, m, temp,
                                             seed), rid
    if case == "resumed":
        assert eng.kv.preemptions > 0
