"""Per-architecture smoke tests (assigned-architecture requirement).

Each assigned architecture is instantiated at its REDUCED same-family config
(``ArchConfig.smoke()``: tiny dims, 2 pattern periods, few experts) and runs
one forward/train step plus a prefill->decode consistency check on CPU,
asserting output shapes and the absence of NaNs.  The FULL configs are only
ever exercised via the dry-run (ShapeDtypeStructs, no allocation).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.models import build_model, init_params, make_batch
from repro.training import OptimizerConfig, init_opt_state, make_train_step

B, S = 2, 64


@pytest.fixture(scope="module")
def built():
    """Cache (model, params, batch) per arch across tests in this module."""
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_config(arch).smoke()
            model = build_model(cfg)
            params = init_params(model.param_specs(), jax.random.PRNGKey(0))
            batch = make_batch(cfg, "train", B, S, seed=1)
            cache[arch] = (cfg, model, params, batch)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_full_config_matches_assignment(arch):
    """The full config carries the exact assigned hyper-parameters."""
    cfg = get_config(arch)
    assigned = {
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
        "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
    }[arch]
    L, d, H, KVH, dff, V = assigned
    assert cfg.n_layers == L
    assert cfg.d_model == d
    assert cfg.n_heads == H
    assert cfg.n_kv_heads == KVH
    assert cfg.vocab_size == V
    if cfg.moe is not None:
        assert cfg.moe.expert_d_ff == dff
    else:
        assert cfg.d_ff == dff
    # family-specific structure
    if arch == "jamba-v0.1-52b":
        assert cfg.pattern.count("A") * 7 == cfg.pattern.count("M")
        assert cfg.moe.num_experts == 16 and cfg.moe.top_k == 2
    if arch == "qwen3-moe-30b-a3b":
        assert cfg.moe.num_experts == 128 and cfg.moe.top_k == 8
    if arch == "grok-1-314b":
        assert cfg.moe.num_experts == 8 and cfg.moe.top_k == 2
    if arch == "qwen3-8b":
        assert cfg.qk_norm
    if arch == "qwen2-72b":
        assert cfg.qkv_bias
    if arch == "olmo-1b":
        assert cfg.norm_type == "layernorm_np"
    if arch == "xlstm-125m":
        assert set(cfg.pattern) <= {"l", "s"}
    if arch == "seamless-m4t-medium":
        assert cfg.encdec and cfg.frontend == "audio"
    if arch == "internvl2-1b":
        assert cfg.frontend == "vision"


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_forward_loss_shapes_and_finite(arch, built):
    cfg, model, params, batch = built(arch)
    loss, metrics = model.loss(params, batch)
    assert loss.shape == ()
    assert jnp.isfinite(loss), f"{arch} loss is not finite"
    assert float(metrics["tokens"]) > 0


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_one_train_step_updates_params(arch, built):
    cfg, model, params, batch = built(arch)
    step_fn = make_train_step(model, OptimizerConfig(learning_rate=1e-3))
    opt_state = init_opt_state(params)
    new_params, new_opt, metrics = jax.jit(step_fn)(params, opt_state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert jnp.isfinite(metrics["grad_norm"])
    assert int(new_opt["step"]) == 1
    # params actually moved and stayed finite
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), params, new_params
    )
    assert max(jax.tree.leaves(moved)) > 0
    for leaf in jax.tree.leaves(new_params):
        assert jnp.all(jnp.isfinite(leaf))


def _prompt_batch(cfg, tokens):
    """A one-row batch of ``tokens`` with the arch's fixed side inputs."""
    T = tokens.shape[1]
    batch = {
        "tokens": tokens,
        "segment_ids": jnp.ones_like(tokens),
        "positions": jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (1, T)),
    }
    rng = np.random.default_rng(4)
    if cfg.encdec:
        batch["enc_embeds"] = jnp.asarray(
            rng.normal(size=(1, 8, cfg.d_model)) * 0.02, jnp.float32
        )
        batch["enc_segment_ids"] = jnp.ones((1, 8), jnp.int32)
    if cfg.frontend == "vision":
        batch["vision_embeds"] = jnp.asarray(
            rng.normal(size=(1, cfg.frontend_tokens, cfg.d_model)) * 0.02,
            jnp.float32,
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_prefill_decode_consistency(arch, built):
    """decode_step after prefill continues the sequence the prefill built:
    each greedy decode step's logits equal the last-token logits of a
    prefill over the prompt plus the tokens decoded so far.  Capacity-binned
    MoE drops different tokens in a batch than one at a time, so those
    archs are held to finite values only."""
    cfg, model, params, _ = built(arch)
    rng = np.random.default_rng(2)
    T, gen = 8, 2
    seq = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(1, T)), jnp.int32)

    logits, cache = model.prefill(params, _prompt_batch(cfg, seq),
                                  max_len=T + gen)
    assert jnp.all(jnp.isfinite(logits))
    shape = logits.shape
    for _ in range(gen):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        logits, cache = model.decode_step(params, {"tokens": tok}, cache)
        assert logits.shape == shape
        assert jnp.all(jnp.isfinite(logits))
        seq = jnp.concatenate([seq, tok], axis=1)
        if cfg.moe is None:
            ref, _ = model.prefill(params, _prompt_batch(cfg, seq),
                                   max_len=seq.shape[1])
            err = jnp.abs(logits - ref).max() / jnp.abs(ref).max()
            assert float(err) < 1e-4


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen3-8b", "xlstm-125m",
                                  "jamba-v0.1-52b"])
def test_decode_matches_prefill_teacher_forced(arch, built):
    """Stronger consistency: running the prompt token-by-token through
    decode_step produces (approximately) the prefill's last-token logits."""
    cfg, model, params, _ = built(arch)
    rng = np.random.default_rng(3)
    T = 6
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(1, T)), jnp.int32)
    batch = {
        "tokens": prompt,
        "segment_ids": jnp.ones_like(prompt),
        "positions": jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (1, T)),
    }
    logits_p, _ = model.prefill(params, batch, max_len=T)

    cache = model.init_cache(1, T + 2, dtype=jnp.float32)
    logits_d = None
    for t in range(T):
        logits_d, cache = model.decode_step(
            params, {"tokens": prompt[:, t : t + 1]}, cache
        )
    np.testing.assert_allclose(
        np.asarray(logits_d), np.asarray(logits_p), rtol=2e-2, atol=2e-2
    )


def test_packed_vs_separate_loss_equivalence():
    """Two documents packed into one row give the same loss as two rows —
    the correctness contract of First-Fit packing + segment masking."""
    cfg = get_config("olmo-1b").smoke()
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    d1 = rng.integers(1, cfg.vocab_size, size=24).astype(np.int32)
    d2 = rng.integers(1, cfg.vocab_size, size=40).astype(np.int32)

    S = 64

    def row(doc, seg_id):
        t = np.zeros(S, np.int32)
        l = np.full(S, -1, np.int32)
        s = np.zeros(S, np.int32)
        p = np.zeros(S, np.int32)
        n = len(doc)
        t[:n] = doc
        l[: n - 1] = doc[1:]
        s[:n] = seg_id
        p[:n] = np.arange(n)
        return t, l, s, p

    # packed: both documents in one row
    tp = np.zeros(S, np.int32)
    lp = np.full(S, -1, np.int32)
    sp = np.zeros(S, np.int32)
    pp = np.zeros(S, np.int32)
    tp[: len(d1)] = d1
    lp[: len(d1) - 1] = d1[1:]
    sp[: len(d1)] = 1
    pp[: len(d1)] = np.arange(len(d1))
    off = len(d1)
    tp[off : off + len(d2)] = d2
    lp[off : off + len(d2) - 1] = d2[1:]
    sp[off : off + len(d2)] = 2
    pp[off : off + len(d2)] = np.arange(len(d2))

    packed = {
        "tokens": jnp.asarray(tp)[None],
        "labels": jnp.asarray(lp)[None],
        "segment_ids": jnp.asarray(sp)[None],
        "positions": jnp.asarray(pp)[None],
    }
    r1, r2 = row(d1, 1), row(d2, 1)
    separate = {
        "tokens": jnp.asarray(np.stack([r1[0], r2[0]])),
        "labels": jnp.asarray(np.stack([r1[1], r2[1]])),
        "segment_ids": jnp.asarray(np.stack([r1[2], r2[2]])),
        "positions": jnp.asarray(np.stack([r1[3], r2[3]])),
    }
    loss_packed, _ = model.loss(params, packed)
    loss_sep, _ = model.loss(params, separate)
    np.testing.assert_allclose(
        float(loss_packed), float(loss_sep), rtol=1e-4
    )


def test_param_counts_match_materialized():
    """Analytic param_counts() agrees with the materialized tree (smoke)."""
    for arch in ("olmo-1b", "qwen3-8b"):
        cfg = get_config(arch).smoke()
        model = build_model(cfg)
        params = init_params(model.param_specs(), jax.random.PRNGKey(0))
        n_real = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        n_analytic, _ = cfg.param_counts()
        # analytic count excludes norm scales and uses the unpadded vocab;
        # require agreement within 5%
        assert abs(n_real - n_analytic) / n_real < 0.05


def test_attention_init_uses_true_fan_in():
    """Each attention projection starts at std 1/sqrt(inputs summed): d_model
    for q/k/v, heads x head_dim for the output.  Scaling by one of the
    head axes instead saturates the softmax, and a random-weight model then
    turns a 1e-6 change of its weights into a different answer."""
    cfg = get_config("olmo-1b").smoke()
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    mixer = params["blocks"]["0"]["mixer"]
    d, hd = cfg.d_model, cfg.head_dim_
    for name, fan_in in (("wq", d), ("wk", d), ("wv", d),
                         ("wo", cfg.n_heads * hd)):
        std = float(jnp.std(mixer[name]))
        assert std == pytest.approx(fan_in ** -0.5, rel=0.1), name


def test_run_local_decode_matches_teacher_forced():
    """The serving driver's greedy decode continues the prefill's cache:
    every step's logits equal decode_step fed the prompt and the served
    tokens one at a time from an empty cache."""
    import argparse

    from repro.launch.serve import run_local

    gen = 4
    out = run_local(argparse.Namespace(arch="olmo-1b", smoke=True,
                                       requests=8, gen_tokens=gen))
    model = build_model(get_config("olmo-1b").smoke())
    prompt = np.asarray(out["batch"]["tokens"])
    fed = np.concatenate([prompt, out["tokens"][:, :gen]], axis=1)
    T = prompt.shape[1]
    step = jax.jit(model.decode_step)
    cache = model.init_cache(fed.shape[0], T + gen, dtype=jnp.float32)
    ref = []
    for t in range(T + gen):
        logits, cache = step(out["params"], {"tokens": fed[:, t:t + 1]}, cache)
        ref.append(np.asarray(logits))
    np.testing.assert_allclose(ref[T - 1], out["prefill_logits"],
                               rtol=1e-4, atol=1e-4)
    assert out["decode_logits"].shape == (fed.shape[0], gen, ref[0].shape[-1])
    np.testing.assert_allclose(out["decode_logits"], np.stack(ref[T:], axis=1),
                               rtol=1e-4, atol=1e-4)


def _per_layer_decode_step(model, params, tok, cache):
    """A plain decode step to hold the scanned one to: a Python loop over
    layers, each attention layer's cache sliced out of the stack, decoded
    by ``attention_decode`` as a one-layer stack, and stacked back."""
    from repro.models import moe as moe_lib, ssm as ssm_lib
    from repro.models import xlstm as xlstm_lib
    from repro.models.layers import KVCache, attention_decode, mlp, norm

    cfg = model.cfg
    step = {"M": ssm_lib.mamba_decode_step, "l": xlstm_lib.mlstm_decode_step,
            "s": xlstm_lib.slstm_decode_step}
    x = jnp.take(params["embed"], tok, axis=0)
    length = cache["len"] + 1
    layers = {key: [] for key in cache["blocks"]}
    for i in range(cfg.n_periods):
        for pos, char in enumerate(cfg.pattern):
            key = str(pos)
            p = jax.tree.map(lambda a: a[i], params["blocks"][key])
            c = jax.tree.map(lambda a: a[i], cache["blocks"][key])
            h = norm(p["ln1"], cfg.norm_type, x)
            if char == "A":
                out, kv = attention_decode(
                    p["mixer"], cfg, h, cache["len"],
                    KVCache(k=c["k"][None], v=c["v"][None]), 0, length)
                c = {"k": kv.k[0], "v": kv.v[0]}
            else:
                out, c = step[char](p["mixer"], cfg, h, c)
            layers[key].append(c)
            x = x + out
            if "ffn" in p:
                h = norm(p["ln2"], cfg.norm_type, x)
                if "router" in p["ffn"]:
                    out, _ = moe_lib.moe_layer(p["ffn"], cfg, h)
                else:
                    out = mlp(p["ffn"], cfg, h)
                x = x + out
    x = norm(params["final_norm"], cfg.norm_type, x)
    logits = x[:, 0].astype(jnp.float32) @ model._table(params).T.astype(
        jnp.float32)
    blocks = {key: jax.tree.map(lambda *a: jnp.stack(a), *per_layer)
              for key, per_layer in layers.items()}
    return logits, {"blocks": blocks, "len": length}


@pytest.mark.parametrize("arch,window", [
    ("olmo-1b", 0),         # dense MHA
    ("qwen3-8b", 3),        # GQA, a sliding window shorter than the context
    ("jamba-v0.1-52b", 0),  # Mamba blocks and MoE beside one attention block
])
def test_decode_step_matches_per_layer_reference(arch, window):
    """The scanned decode step, its K/V stacks carried through the layer
    scan and written in place, equals a per-layer loop over sliced caches:
    the same logits and caches, each step writing exactly row ``len - 1``
    of every layer's K and V and leaving the rows past ``len`` zero."""
    import dataclasses

    cfg = dataclasses.replace(get_config(arch).smoke(), sliding_window=window)
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    B, max_len, steps = 3, 12, 6
    cache = model.init_cache(B, max_len, dtype=jnp.float32)
    cache["len"] = jnp.asarray([0, 3, 5], jnp.int32)  # rows at different points
    ref_cache = cache
    step = jax.jit(model.decode_step)
    ref_step = jax.jit(lambda p, t, c: _per_layer_decode_step(model, p, t, c))
    attn = [str(pos) for pos, c in enumerate(cfg.pattern) if c == "A"]
    rows = np.arange(max_len)
    for _ in range(steps):
        tok = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(B, 1)),
                          jnp.int32)
        old = jax.tree.map(np.asarray, cache)
        logits, cache = step(params, {"tokens": tok}, cache)
        ref_logits, ref_cache = ref_step(params, tok, ref_cache)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                                   rtol=0, atol=1e-6)
        for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(ref_cache)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=1e-6)
        new_len = np.asarray(cache["len"])
        np.testing.assert_array_equal(new_len, old["len"] + 1)
        for key in attn:
            for name in ("k", "v"):
                new, before = np.asarray(cache["blocks"][key][name]), \
                    old["blocks"][key][name]
                for b in range(B):
                    written = rows == new_len[b] - 1
                    np.testing.assert_array_equal(new[:, b, ~written],
                                                  before[:, b, ~written])
                    assert np.all(np.any(new[:, b, written] != 0,
                                         axis=(-2, -1)))
                    assert not np.any(new[:, b, new_len[b]:])


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen3-8b", "qwen3-moe-30b-a3b",
                                  "jamba-v0.1-52b"])
def test_only_decode_step_unrolls_its_layer_loop(arch, built):
    """``prefill`` and the train step lower their layer scan to a ``while``
    (so their HLO stays one period long), while ``decode_step`` lowers to
    straight-line layers: dense, GQA, MoE and hybrid alike."""
    cfg, model, params, batch = built(arch)
    prompt = {k: v for k, v in batch.items() if k != "labels"}
    programs = {
        "prefill": jax.jit(lambda p, b: model.prefill(p, b, max_len=S + 2))
        .lower(params, prompt),
        "train_step": jax.jit(make_train_step(model, OptimizerConfig()))
        .lower(params, init_opt_state(params), batch),
        "decode_step": jax.jit(model.decode_step).lower(
            params, {"tokens": batch["tokens"][:, :1]},
            model.init_cache(B, S + 2, dtype=jnp.float32)),
    }
    # a while's location is its scope: ``.../layers/while``, and under
    # autodiff ``.../jvp(layers)/while``, ``transpose(jvp(layers))/while``
    layer_loop = re.compile(r'loc\("[^"]*\blayers\)*/while"')
    for name, lowered in programs.items():
        text = lowered.as_text(debug_info=True)
        assert (layer_loop.search(text) is None) == (name == "decode_step"), \
            name
