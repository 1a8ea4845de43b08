"""Each configuration's architecture comes from its adapter module.

The decoder's adapter gives what the harness gave before it: the pinned
values below were read from the harness and ``bench/weights.py`` as they
were before the adapter held them (the same ModelConfig fields, the same
weights bit for bit, the same FLOPs, the same kernel seconds on the
recorded traces).  A test-only adapter with a mixed per-layer pattern
(``mixed_adapter.py``) runs through the harness on the CPU, and the
metrics' counts follow its layers, not ``num_hidden_layers``.
"""
import hashlib
import json
import shutil
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import devtrace, harness
from bench.configs import decoder_adapter
from bench.counts import kernels
from bench.metrics.context import Context
from bench.tests import mixed_adapter
from bench.tests.conftest import BENCH, TINY_LIMIT, tiny_cell, tiny_run

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DATA = BENCH / "tests" / "data"

# the ModelConfig fields the adapter sets, blocks as (kind, layers)
CONFIGS = {
    "smollm-135m": dict(
        num_layers=30, d_model=576, num_heads=9, num_kv_heads=3, head_dim=64,
        d_ff=1536, vocab_size=49152, blocks=("attn+mlp", 30), window_size=0,
        tie_embeddings=True, rope_theta=10000.0, norm_eps=1e-05,
        dtype="bfloat16", param_dtype="bfloat16", use_pallas_prefill=True,
        use_pallas_decode=True),
    "h2o-danube-1.8b": dict(
        num_layers=24, d_model=2560, num_heads=32, num_kv_heads=8,
        head_dim=80, d_ff=6912, vocab_size=32000, blocks=("swa+mlp", 24),
        window_size=4096, tie_embeddings=False, rope_theta=10000.0,
        norm_eps=1e-05, dtype="bfloat16", param_dtype="bfloat16",
        use_pallas_prefill=True, use_pallas_decode=True),
}
# prefill_flops(cfg, 128), then decode_flops at positions 128, 151, 255
FLOPS = {
    "smollm-135m": [27806367744.0, 277876224.0, 279465984.0, 286654464.0],
    "h2o-danube-1.8b": [429005209600.0, 3530014720.0, 3535667200.0,
                        3561226240.0],
}
# sha256 (first 16 hex digits) of each leaf's bytes: the tiny cell's
# global leaves, and each layer leaf at layer 0 and at the last layer
WEIGHTS = {
    3141592601: {
        "embed": "530abb2cfb04a7e5", "final_norm": "79935755b4d0eb93",
        "head": "1bc757a841b56a5c",
        "attn/wk@0": "9699a5784f6e2f3d", "attn/wk@last": "7905c1b59c9891c6",
        "attn/wo@0": "618ce43f22210e2e", "attn/wo@last": "94b70b582528595f",
        "attn/wq@0": "0be453146272c31a", "attn/wq@last": "0098c8d0c6295b0d",
        "attn/wv@0": "1d800912b522dd15", "attn/wv@last": "2684cf74fc077a75",
        "mlp/w_down@0": "25c1745826306a6a",
        "mlp/w_down@last": "7b21651a82d024d0",
        "mlp/w_gate@0": "86f698c77180983b",
        "mlp/w_gate@last": "454be47f74c8b76c",
        "mlp/w_up@0": "bee33eab06b78afe", "mlp/w_up@last": "3754d582fd3db695",
        "norm1@0": "b790ba75d12e24da", "norm1@last": "e22c0902d6e936be",
        "norm2@0": "db604e7295171ca7", "norm2@last": "4aa501d0d57add85"},
    -7: {
        "embed": "51fc54e82171b926", "final_norm": "d4e6cfeebbcdd2e6",
        "head": "4b9253472ddb946a",
        "attn/wk@0": "703d176cf4b67440", "attn/wk@last": "2a3b080e7f0c26a2",
        "attn/wo@0": "59fe1e6c541c698a", "attn/wo@last": "8085bb24358be6cc",
        "attn/wq@0": "44e913157ff1bc44", "attn/wq@last": "126844c38d382231",
        "attn/wv@0": "c949c137e19d8198", "attn/wv@last": "66a3edb55c4f3751",
        "mlp/w_down@0": "f3b7d3b7150f5c8e",
        "mlp/w_down@last": "59a6ae693f4da137",
        "mlp/w_gate@0": "64691e5d4b22fe35",
        "mlp/w_gate@last": "8c6b8c478653e566",
        "mlp/w_up@0": "8480a93d032b05bc", "mlp/w_up@last": "887cfaa58fb581ae",
        "norm1@0": "f18907ac3517200a", "norm1@last": "6defcbd4a7c328e6",
        "norm2@0": "6aa0ac33141b700f", "norm2@last": "a4c04d20aaa7850c"},
}
# kernel seconds per program, as the reduction keyed them by program alone
KERNEL_S = {
    "smollm135m-chat.slice": {"prefill": 0.0006210799999999998,
                              "decode": 0.0021778359999999977},
    "smollm135m-chat-over.program": {"prefill": 0.0006210789999999999,
                                     "decode": 0.0023973779999999965},
}


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _digest(x):
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


# -- the decoder's adapter is a pure move ----------------------------------
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_program_config_pinned(name):
    cfg = _config(name)
    pc = decoder_adapter.program_config(cfg)
    got = {k: getattr(pc, k) for k in CONFIGS[name]}
    kinds = set(pc.blocks)
    got["blocks"] = (kinds.pop(), len(pc.blocks))
    assert not kinds
    assert got == CONFIGS[name]


@pytest.mark.parametrize("name", sorted(FLOPS))
def test_model_flops_pinned(name):
    cfg = _config(name)
    got = [decoder_adapter.prefill_flops(cfg, 128)] + [
        decoder_adapter.decode_flops(cfg, p) for p in (128, 151, 255)]
    assert got == FLOPS[name]


@pytest.mark.parametrize("seed", sorted(WEIGHTS))
def test_weights_pinned(seed):
    tree = decoder_adapter.program_params(seed, tiny_cell().cfg)
    got = {k: _digest(tree[k]) for k in ("embed", "final_norm", "head")}
    (group,) = tree["groups"]
    for path, leaf in jax.tree_util.tree_flatten_with_path(group)[0]:
        k = "/".join(str(p.key) for p in path)
        got[f"{k}@0"], got[f"{k}@last"] = _digest(leaf[0]), _digest(leaf[-1])
    assert got == WEIGHTS[seed]


@pytest.mark.parametrize("trace", sorted(KERNEL_S))
def test_kernel_seconds_by_name_sum_to_program(trace):
    ks = devtrace.reduce_trace(str(DATA / f"{trace}.xplane.pb"))["kernel_s"]
    for prog, want in KERNEL_S[trace].items():
        got = sum(v for (mod, _), v in ks.items() if prog in mod)
        assert got == pytest.approx(want, abs=1e-9)
    names = sorted(k for _, k in ks)
    assert names[0] == "decode_attention"
    assert names[1].startswith("swa_prefill")


def test_kernels_follow_the_configured_guard():
    assert decoder_adapter.kernels(_config("smollm-135m")) == {
        "prefill": ["swa_prefill"], "decode": ["decode_attention"]}
    danube = _config("h2o-danube-1.8b")
    assert decoder_adapter.kernels(danube) == {"prefill": ["swa_prefill"],
                                               "decode": []}
    assert decoder_adapter.attention(danube) == (32, 8, 80,
                                                 {"swa_prefill": 24}, 4096)


# -- a configuration names its adapter --------------------------------------
@pytest.mark.parametrize("adapter", [None, "no_such_adapter", "../weights"])
def test_configuration_without_adapter_is_refused(tmp_path, adapter):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    for sub in ("configs", "traffic", "limits"):
        shutil.copytree(BENCH / sub, tmp_path / "bench" / sub)
    path = tmp_path / "bench" / "configs" / "smollm-135m.json"
    cfg = json.loads(path.read_text())
    if adapter is None:
        del cfg["adapter"]
    else:
        cfg["adapter"] = adapter
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match='"adapter"'):
        harness.load_cell("smollm135m-chat", tmp_path)
    assert harness.load_cell("danube1.8b-chat", tmp_path).adapter is \
        decoder_adapter


# -- the kernel guard looks for each kernel by name -------------------------
class _Entry:
    """Stands in for a table entry: ``func.lower(...).as_text()`` gives a
    fixed text, and calling it gives a token and a cache."""

    def __init__(self, text):
        self.args, self.func, self.text = (), self, text

    def lower(self, *_):
        return self

    def as_text(self):
        return self.text

    def __call__(self, tokens):
        return tokens[:, 0], tokens


def test_kernel_guard_names_each_kernel():
    def text(*names):
        return " ".join(f'stablehlo.custom_call @tpu_custom_call(%a) '
                        f'{{kernel_name = "{n}"}}' for n in names)

    pre = {(1, 2): _Entry(text("swa_prefill"))}
    expect = {"prefill": ["swa_prefill"], "decode": ["decode_attention"]}
    dec = {(1, 2): _Entry(text("decode_attention"))}
    assert harness.kernel_guard(pre, dec, 8, expect) == []
    # a custom call of another kernel does not stand in for the one named
    dec = {(1, 2): _Entry(text("ssd_scan"))}
    assert harness.kernel_guard(pre, dec, 8, expect) == [
        "decode decode_attention b=2"]
    assert harness.kernel_guard(pre, dec, 8, dict(expect, decode=[])) == []


# -- an architecture enters as files only -----------------------------------
def _mixed_cell():
    cell = tiny_cell(limit=TINY_LIMIT)
    cell.cfg.update(num_hidden_layers=len(mixed_adapter.PATTERN),
                    adapter="mixed_adapter")
    cell.adapter = mixed_adapter
    return cell


@pytest.fixture(scope="module")
def mixed():
    return tiny_run(5, cell=_mixed_cell())


def test_mixed_pattern_is_served_through_the_harness(mixed):
    run, res = mixed
    checks = res["checks"]
    assert checks["unserved"]["value"] == 0, checks
    assert checks["wrong_tokens"]["value"] == 0, checks
    assert run.window_rows().size > 0
    assert harness.correct(checks), checks
    cfg = run.cell.cfg
    pc = mixed_adapter.program_config(cfg)
    from repro.models import build_model
    from repro.models.transformer import layer_groups
    assert layer_groups(pc) == [("swa+mlp", 1), ("attn+mlp", 1),
                                ("swa+mlp", 1)]
    expected = jax.eval_shape(build_model(pc).init, jax.random.key(0))
    assert len(mixed_adapter.program_params(5, cfg, expected)["groups"]) == 3


def test_metric_counts_follow_the_adapter(mixed):
    run, _ = mixed
    ctx = Context(run, PEAKS)
    cfg = run.cell.cfg
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    w = cfg["sliding_window"]
    p = ctx.prompt_len
    span = ctx.window
    dec = ctx.calls("decode", span)
    pre = ctx.calls("prefill", span)
    assert dec and pre
    # the decode kernel runs in the one full layer, the prefill kernel in
    # all three
    want = np.sum([kernels.decode_attention(b, h, kv, hd, p + step)
                   for b, _, _, step, _ in dec], axis=0)
    assert ctx.decode_attention_work(span) == pytest.approx(tuple(want))
    want = np.sum([kernels.swa_prefill(b, h, kv, hd, p, w)
                   for b, _, _, _, _ in pre], axis=0) * 3
    assert ctx.swa_prefill_work(span) == pytest.approx(tuple(want))
    a, b = span
    want = sum(mixed_adapter.prefill_flops(cfg, p) if j == 0
               else mixed_adapter.decode_flops(cfg, p + j - 1)
               for s in run.rec.stamps.values() for j, t in enumerate(s)
               if a <= t < b)
    assert ctx.served_model_flops() == pytest.approx(want)
    # one FLOP per first token, none per later one: the count is the
    # adapter's, whatever the configuration file says
    ctx.adapter = SimpleNamespace(prefill_flops=lambda c, p: 1.0,
                                  decode_flops=lambda c, q: 0.0)
    assert ctx.served_model_flops() == sum(
        1 for s in run.rec.stamps.values() if s and a <= s[0] < b)
