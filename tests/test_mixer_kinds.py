"""One declaration a mixer kind (``models.hybrid.MIXER_KINDS``), one answer
to what a model's caches may be combined with (``kvcache.pool.cache_plan``)
and one hook a launch for what the host counts
(``models.hybrid.launch_counters``), in pure Python: configs and fakes, no
model built, no program compiled.  The wording the refusals are held to is
what the per-model modules pin through a whole engine (``test_nemotron_h``,
``test_minicpm_sala``, ``test_xing4``, ``test_brumby``, ``test_smallthinker``,
``test_lfm2_moe``)."""

import ast
import pathlib
import types

import numpy as np
import pytest
from flax import linen as nn

from neuronx_distributed_tpu.kvcache import pool
from neuronx_distributed_tpu.models import hybrid
from neuronx_distributed_tpu.models.llama import LlamaConfig
from neuronx_distributed_tpu.obs import MetricRegistry
from neuronx_distributed_tpu.obs.schemas import REGISTRY_METRICS
from neuronx_distributed_tpu.ops.block_select import selection_counts
from neuronx_distributed_tpu.parallel import moe

PKG = pathlib.Path(hybrid.__file__).resolve().parents[1]
T = 96


def config(*mixers, **kw):
    return LlamaConfig.tiny(
        num_layers=len(mixers), mixer_types=mixers, ssm_heads=4,
        ssm_head_dim=8, kv_lora_rank=16, num_heads=4, num_kv_heads=2,
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_head_dim=8,
        gdn_value_head_dim=8,
        sparse_block_size=4, sparse_kernel_size=4, sparse_kernel_stride=2,
        sparse_window_size=4, sparse_topk=3, sparse_dense_len=8, **kw)


# ---------------------------------------------------------------------------
# (a) the table: every record is whole, and every other spelling reads it
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", hybrid.MIXERS)
def test_a_kind_is_one_whole_record(name):
    kind = hybrid.MIXER_KINDS[name]
    assert kind.name == name and hybrid.CACHE_OF[name] == kind.cache
    assert kind.cache in pool.CACHE_KINDS
    cfg = config(name, "attention")
    assert cfg.layer_caches == (kind.cache, "pages")
    # a state function exactly where the layer keeps a state row
    assert (kind.state is not None) == (kind.cache == "state")
    if kind.state is not None:
        arrays = hybrid.state_arrays(cfg, name)
        assert arrays == cfg.state_arrays and arrays
        for shape, dtype in arrays:
            assert all(isinstance(n, int) and n > 0 for n in shape)
            np.dtype(dtype)
        assert name in hybrid.RECURRENT_NAMES.split(", ")
    # the block's own attention and the absent mixer have no module
    assert (kind.module is None) == (name in ("attention", "none"))
    if kind.module is not None:
        built = hybrid.hybrid_mixer(cfg, name)
        assert type(built) is kind.module and built.name == "attn"
        assert issubclass(kind.module, nn.Module)
    else:
        with pytest.raises(ValueError, match="unknown mixer"):
            hybrid.hybrid_mixer(cfg, name)
    # what only a recurrent kind may say of itself
    if kind.cache != "state":
        assert not kind.rows_in_place and kind.stepped is None
        assert kind.skipped is None
    # no cached call: nothing kept, and the sentence that says so
    if kind.unserved is not None:
        assert kind.cache == "none" and "no cached call" in kind.unserved
    # its counters are on the schema's floor
    if kind.counted is not None:
        for family in ("chunk", "step"):
            assert REGISTRY_METRICS[
                f"serving/{kind.counted}_tokens_total/{family}"] == "counter"
    assert hybrid.kinds_of(cfg) == tuple(
        k for k in hybrid.MIXER_KINDS.values()
        if k.name in (name, "attention"))


def test_no_layer_list_names_no_kind_and_two_recurrent_kinds_are_refused():
    assert hybrid.kinds_of(LlamaConfig.tiny()) == ()
    assert hybrid.kinds_of(None) == ()
    with pytest.raises(ValueError, match="one kind of recurrent layer"):
        config("mamba2", "lightning-attn")
    with pytest.raises(ValueError, match="names one of"):
        config("mamba3")


# ---------------------------------------------------------------------------
# (b) cache kind x what is asked of it
# ---------------------------------------------------------------------------


def caches(*kept, windows=None):
    """A config as ``cache_plan`` reads one: what each layer keeps, and the
    layers' windows."""
    seen = list(dict.fromkeys(windows or ()))
    return types.SimpleNamespace(
        layer_caches=kept, layer_windows=windows,
        page_kind_of_layer=tuple(seen.index(w) for w in windows or ()))


def plan(cfg, **asked):
    return pool.cache_plan(cfg, **{**dict(
        spec_k=0, kv_quant=None, adapters=False, prefix_cache=None, tp=1,
        max_total_len=T), **asked})


ASKS = {
    "nothing": ({}, None),
    "spec_k": (dict(spec_k=2), "speculative"),
    "kv_quant": (dict(kv_quant="int8"), "int8"),
    "adapter_store": (dict(adapters=True), "LoRA"),
    "tp": (dict(tp=2), "tensor parallelism"),
    "prefix_cache": (dict(prefix_cache=True), "prefix index"),
}
# the model of each cache kind (a layer that keeps nothing sits beside one
# that keeps pages), whether its index stays on, and what its migration says
MODELS = {
    "pages": (("pages", "pages"), True, None),
    "selected_pages": (("selected_pages", "pages"), False, "state rows"),
    "state": (("state", "state"), False, "recurrent"),
    "latent": (("latent", "latent"), True, "latent pages"),
    "none": (("none", "pages"), True, None),
}


@pytest.mark.parametrize("ask", sorted(ASKS))
@pytest.mark.parametrize("kind", pool.CACHE_KINDS)
def test_what_a_cache_kind_carries(kind, ask):
    kept, shares, unmoved = MODELS[kind]
    asked, wording = ASKS[ask]
    carried = kind in ("pages", "none")
    pageless = kind == "state"
    refused = ask != "nothing" and not carried and (
        ask != "prefix_cache" or pageless)
    assert (ask == "nothing" or ask in pool.CARRIES[kind]) == (
        carried or ask == "nothing" or (ask == "prefix_cache" and shares))
    if refused:
        with pytest.raises(ValueError, match=wording) as e:
            plan(caches(*kept), **asked)
        assert str(e.value).startswith(
            "not carried through recurrent (lightning-attn, mamba2, "
            "power-retention, gated-delta), page-selecting or latent "
            "layers yet: ")
        return
    got = plan(caches(*kept), **asked)
    assert got.recurrent == (kind == "state") and got.pageless == pageless
    assert len(got.page_kinds) == 1
    # the index: on where pages are shareable, whatever was passed
    assert got.prefix_cache == shares
    # one kind without a window: nothing to give back either way
    assert got.free_behind == (ask in ("nothing", "tp") or (
        ask == "prefix_cache" and not shares))
    why = got.refuses_migration(False)
    if unmoved is None:
        assert why is None
    else:
        assert unmoved in why


def test_every_refused_ask_is_named_in_one_error():
    with pytest.raises(ValueError) as e:
        plan(caches("state"), spec_k=2, kv_quant="int8", adapters=True, tp=4,
             prefix_cache=True)
    assert str(e.value).split(": ", 1)[1] == (
        "speculative decoding (spec_k): no state roll-back; an int8 page "
        "pool (kv_quant); LoRA adapter pages (adapter_store); tensor "
        "parallelism (tp > 1); the prefix index (prefix_cache=True): a "
        "model that keeps no page has no chain to share")
    # a hybrid of state rows and pages is asked for the index: off, unsaid
    assert plan(caches("state", "pages"),
                prefix_cache=True).prefix_cache is False


W = 16
WINDOWS = {
    # windows a layer -> ask -> (free_behind, prefix_cache), or the refusal
    "no_window": ((None, None), {
        "nothing": (True, True), "prefix_cache": (False, True),
        "kv_quant": (False, True), "spec_k": (False, True),
        "adapter_store": (False, True)}),
    "one_window": ((W, W), {
        "nothing": (True, False), "prefix_cache": (False, True),
        "kv_quant": (False, True), "spec_k": (False, True),
        "adapter_store": (False, True)}),
    "window_past_the_row": ((T, T), {
        "nothing": (True, True), "prefix_cache": (False, True),
        "kv_quant": (False, True), "spec_k": (False, True),
        "adapter_store": (False, True)}),
    "two_kinds": ((None, W), {
        "nothing": (True, False), "prefix_cache": "prefix index",
        "kv_quant": "int8", "spec_k": "speculative",
        "adapter_store": "LoRA"}),
}


@pytest.mark.parametrize("ask", ["nothing", "prefix_cache", "kv_quant",
                                 "spec_k", "adapter_store"])
@pytest.mark.parametrize("model", sorted(WINDOWS))
def test_whole_chains_or_pages_given_back(model, ask):
    """The tri-state the engine's constructor held (ROADMAP D15), as it
    was: a model of one kind keeps whole chains where they are asked for,
    a model of several kinds refuses."""
    windows, want = WINDOWS[model]
    cfg = caches("pages", "pages", windows=windows)
    if isinstance(want[ask], str):
        with pytest.raises(ValueError, match=want[ask]) as e:
            plan(cfg, **ASKS[ask][0])
        assert str(e.value).startswith(
            "not carried through pages of several kinds")
        return
    got = plan(cfg, **ASKS[ask][0])
    assert (got.free_behind, got.prefix_cache) == want[ask]
    assert len(got.page_kinds) == len(set(windows))
    frees = got.free_behind and any(w is not None and w < T for w in windows)
    why = got.refuses_migration(frees)
    if model == "two_kinds":
        assert "several kinds" in why
    elif frees:
        assert "prefix_cache=True" in why
    else:
        assert why is None


# ---------------------------------------------------------------------------
# (c) what the host counts of a launch
# ---------------------------------------------------------------------------

DECODE = hybrid.Launch("decode_pages", np.array([6, 17, 2]),
                       np.array([7, 18, 3]), 28, 3, 5)
CHUNK = hybrid.Launch("prefill_chunk_pages", np.arange(8, 16), 20, 16, 8, 12)
COUNTED = {
    "attention": {},
    "lightning-attn": {},
    "conv": {},
    "mamba2": {
        "serving/ssm_tokens_total/step": 3,
        "serving/ssm_tokens_total/chunk": 8,
        "serving/ssm_state_rows_stepped_total": 3,
        # a decode launched over 5 slots of which 3 decode; a chunk's pads
        # are no state rows
        "serving/ssm_state_rows_skipped_total": 2},
    "power-retention": {
        "serving/retention_tokens_total/step": 3,
        "serving/retention_tokens_total/chunk": 8},
    "mla": {
        "serving/latent_tokens_read_total": 28 + 16,
        "serving/latent_tokens_read_total/decode_pages": 28,
        "serving/latent_tokens_read_total/prefill_chunk_pages": 16,
        "kvcache/latent_rows_written_total": 3 + 8,
        "kvcache/latent_rows_written_total/decode_pages": 3,
        "kvcache/latent_rows_written_total/prefill_chunk_pages": 8,
        "serving/latent_tokens_expanded_total": 16},
}


@pytest.mark.parametrize("name", sorted(COUNTED))
def test_a_launch_is_counted_by_the_kinds_the_model_has(name, monkeypatch):
    monkeypatch.setattr(hybrid, "MLA_EXPANDED_MIN_ROWS", 8)
    reg = MetricRegistry()
    counters = hybrid.launch_counters(config(name, "attention"), reg, 8)
    assert bool(counters) == bool(COUNTED[name])
    # the token counters are there at zero before anything ran; no other
    assert reg.snapshot() == {k: 0 for k in COUNTED[name]
                              if "_tokens_total/" in k and "latent" not in k}
    for launch in (DECODE, CHUNK):
        for count in counters:
            assert not count(launch)        # no span key of theirs
    assert reg.snapshot() == COUNTED[name]


def test_a_selecting_model_counts_blocks_and_names_the_span_key():
    cfg = config("minicpm4", "attention")
    spec = cfg.selection_spec
    reg = MetricRegistry()
    (count,) = hybrid.launch_counters(cfg, reg, 8)

    def keys(p, n):
        """The keys one query at ``p`` of a row ``n`` long attends: whole
        blocks before its own, and its own block up to itself."""
        visible = p // spec.block_size + 1
        chosen = visible if n < spec.dense_len else min(visible, spec.topk)
        return (chosen - 1) * spec.block_size + p % spec.block_size + 1

    want = {}
    for launch in (DECODE, CHUNK):
        chosen, visible, dense = selection_counts(
            launch.positions, launch.lengths, spec)
        for key, n in (("selected", chosen), ("visible", visible)):
            for name in (f"serving/sparse_blocks_{key}_total",
                         f"serving/sparse_blocks_{key}_total/"
                         + launch.family):
                want[name] = want.get(name, 0) + n
        want["serving/sparse_dense_queries_total"] = want.get(
            "serving/sparse_dense_queries_total", 0) + dense
        got = count(launch)
        # a decode: every live slot's one query; a chunk: its last row's
        assert got == {"selected_tokens": sum(
            keys(int(p), int(n)) for p, n in zip(DECODE.positions,
                                                 DECODE.lengths))
            if launch is DECODE else keys(15, 20)}
    assert reg.snapshot() == want


def test_a_model_without_a_layer_list_counts_nothing_and_builds_nothing():
    reg = MetricRegistry()
    assert hybrid.launch_counters(LlamaConfig.tiny(), reg, 8) == ()
    assert hybrid.launch_counters(None, reg, 8) == ()
    assert reg.snapshot() == {}


def test_expert_loads_are_booked_once_a_fetch_by_the_book():
    class Routed:
        def __init__(self):
            self.ran = [{"program": "warm", "seq": 0,
                         "load": np.ones((2, 4), np.int64)}]

        def take_moe_stats(self, upto=None):
            out = [s for s in self.ran if upto is None or s["seq"] <= upto]
            self.ran = [s for s in self.ran if s not in out]
            return out

    model, reg = Routed(), MetricRegistry()
    book = moe.ExpertLoadBook(model, reg)
    assert model.ran == [] and reg.snapshot() == {}     # not this engine's
    model.ran = [
        {"program": "decode_pages", "seq": 1, "choice": None,
         "load": np.array([[2, 0, 0, 0], [1, 1, 0, 0]])},
        {"program": "prefill_chunk_pages", "seq": 2,
         "load": np.array([[1, 1, 1, 1], [4, 0, 0, 0]])}]
    programs, loads = book.take(upto=1)
    assert programs == ["decode_pages"] and list(loads[0]) == ["load"]
    book.book(programs, loads)
    snap = reg.snapshot()
    assert snap["moe/assignments_total"] == 4
    assert snap["moe/experts_hit_total/decode_pages"] == 3
    assert snap["moe/gmm_lowered_total/whole_k"] >= 0
    book.book(*book.take())
    snap = reg.snapshot()
    assert snap["moe/assignments_total"] == 12
    assert snap["moe/layer_calls_total/prefill_chunk_pages"] == 2
    # the busiest expert over the mean, a layer, since the book began:
    # loads [[3, 1, 1, 1], [5, 1, 0, 0]] -> (3 / 1.5 + 5 / 1.5) / 2
    assert snap["moe/expert_load_max_over_mean"] == pytest.approx(8 / 3)


# ---------------------------------------------------------------------------
# the seam stays where it was put: the engines ask, they do not name
# ---------------------------------------------------------------------------

DISTINCT = ("minicpm4", "lightning-attn", "mamba2", "power-retention",
            "selected_pages")


def literals(path):
    """The string constants of a source file that are neither docstrings
    nor the keys of a dict display."""
    tree = ast.parse(path.read_text())
    skip = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and isinstance(
                    body[0].value, ast.Constant):
                skip.add(id(body[0].value))
        elif isinstance(node, ast.Dict):
            skip.update(id(k) for k in node.keys)
    return [(n.lineno, n.value) for n in ast.walk(tree)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)
            and id(n) not in skip]


@pytest.mark.parametrize("source", ["serving/engine.py", "trace/engine.py"])
def test_the_engines_name_no_mixer_and_no_cache_kind(source):
    names = set(hybrid.MIXERS) | set(pool.CACHE_KINDS)
    named = [(line, s) for line, s in literals(PKG / source)
             if s in names or any(d in s for d in DISTINCT)]
    assert named == []
    text = (PKG / source).read_text()
    for gone in ("_count_selection", "_count_latents", "_count_moe",
                 "_take_moe_loads", "_ssm", "_retention", "_token_counts"):
        assert gone not in text, gone


def test_the_config_asks_the_table_which_kinds_are_recurrent():
    named = [(line, s) for line, s in literals(PKG / "models/llama.py")
             if any(d in s for d in ("mamba2", "power-retention",
                                     "lightning-attn"))]
    assert named == []
