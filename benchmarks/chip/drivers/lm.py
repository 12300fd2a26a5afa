"""Driver for a decoder language-model cell: greedy prefill and decode.

The timed path is the program's ``DecoderLM.prefill(max_len=...)`` and
``DecoderLM.decode_step``, each jitted with the greedy choice of the next
token, on weights that this file makes from the seed.  A cycle serves one
static batch of the traffic's prompts: prefill gives every sequence its
first token, then ``gen_tokens - 1`` decode steps give the rest, and every
step's tokens are copied to the host, where a server would send them.
Whole cycles follow each other until the window's time is up, so the
window ends at the first cycle boundary past it: a cycle's prompt tokens
count at once, and a window cut inside a cycle would count them for a
part of its time.

``correct``: a sample of the served sequences, drawn from the seed, is
read by the plain float32 reference (``reference/olmo.py``), and the
widest gap by which a served token's reference logit lies below the
reference's best must stay under the cell's limit.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

import numpy as np

import flops
import generator
import harness
from harness import Check, Outcome, Profile
from reference.olmo import served_gaps


def arch(config: Dict[str, Any]):
    from repro.configs.base import ArchConfig

    return ArchConfig(**config["model"])


def weight_shapes(m: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    d, H, KVH, f, L = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["d_ff"], m["n_layers"])
    hd = m.get("head_dim") or d // H
    return {"wq": (L, d, H, hd), "wk": (L, d, KVH, hd), "wv": (L, d, KVH, hd),
            "wo": (L, H, hd, d), "w_gate": (L, d, f), "w_up": (L, d, f),
            "w_down": (L, f, d)}


def fan_in(name: str, m: Dict[str, Any]) -> int:
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    return {"wo": m["n_heads"] * hd, "w_down": m["d_ff"]}.get(name, m["d_model"])


def make_weights(config: Dict[str, Any], seed: int, table_rows: int):
    """The weights in the reference's layout, float32, made on the device
    in one jitted call: normal with std 1/sqrt(fan-in), the embedding
    table with the config's ``embed_std``.  Rows of the table past the
    model's ``vocab_size`` (padding the program adds) are zero."""
    import jax
    import jax.numpy as jnp

    m = config["model"]
    shapes = weight_shapes(m)

    def make(key):
        keys = jax.random.split(key, len(shapes) + 1)
        emb = config["embed_std"] * jax.random.normal(
            keys[0], (table_rows, m["d_model"]), jnp.float32)
        emb = jnp.where(jnp.arange(table_rows)[:, None] < m["vocab_size"],
                        emb, 0.0)
        out = {"embed": emb}
        for k, (name, shape) in zip(keys[1:], shapes.items()):
            out[name] = jax.random.normal(k, shape, jnp.float32) / np.sqrt(
                fan_in(name, m))
        return out

    return jax.jit(make)(harness.jax_key(seed, 3))


def program_params(model, w: Dict[str, Any]):
    """The same arrays in the program's parameter tree (checked against
    the program's own specs, so a layout change fails loudly)."""
    import jax

    from repro.models import abstract_params

    tree = {"embed": w["embed"], "final_norm": {}, "blocks": {"0": {
        "ln1": {}, "ln2": {},
        "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
        "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}}}
    want = abstract_params(model.param_specs())
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's parameter layout changed; "
                           "program_params no longer matches it")
    return tree


def serving_programs(model, max_len: int):
    """The two jitted programs the window drives."""
    import jax
    import jax.numpy as jnp

    def greedy(logits):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]

    def prefill(params, batch):
        logits, cache = model.prefill(params, batch, max_len=max_len)
        return greedy(logits), cache

    def step(params, tok, cache):
        logits, cache = model.decode_step(params, {"tokens": tok}, cache)
        return greedy(logits), cache

    return jax.jit(prefill), jax.jit(step, donate_argnums=(2,))


def prompt_batch(prompts: np.ndarray):
    import jax.numpy as jnp

    B, S = prompts.shape
    return {"tokens": jnp.asarray(prompts),
            "segment_ids": jnp.ones((B, S), jnp.int32),
            "positions": jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                          (B, S))}


class Served:
    """What one cycle served: its prompts and each step's tokens."""

    def __init__(self, prompts: np.ndarray):
        self.prompts = prompts
        self.steps: List[np.ndarray] = []

    def tokens(self, row: int) -> np.ndarray:
        return np.array([s[row] for s in self.steps], np.int32)


def sample(served: List[Served], gen_tokens: int, n: int,
           seed: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """``(prompt, served tokens)`` of ``n`` sequences drawn from the seed
    among those that got all their tokens."""
    done = [c for c in served if len(c.steps) == gen_tokens]
    B = len(done[0].prompts)
    picks = harness.rng(seed, 4).choice(len(done) * B,
                                        size=min(n, len(done) * B),
                                        replace=False)
    return [(done[i // B].prompts[i % B], done[i // B].tokens(i % B))
            for i in picks]


def widest_gap(weights, m: Dict[str, Any], seqs, gaps_fn) -> float:
    """The widest gap ``gaps_fn`` finds over the sampled sequences."""
    cfg = {"rope_theta": m["rope_theta"]}
    return max(float(gaps_fn(weights, cfg, p, t, m["vocab_size"]).max())
               for p, t in seqs)


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t0: float, devs) -> Outcome:
    from repro.models import build_model, pad_vocab

    t_enter = time.perf_counter()
    cfg, traffic = cell.config, cell.traffic
    m = cfg["model"]
    model = build_model(arch(cfg))
    B, P, G = traffic["batch"], traffic["prompt_len"], traffic["gen_tokens"]
    max_len = P + G - 1
    weights = make_weights(cfg, seed, pad_vocab(m["vocab_size"]))
    params = program_params(model, weights)
    prefill, step = serving_programs(model, max_len)
    vocab = int(cfg["prompt_vocab"])
    np.asarray(weights["embed"][0, 0])
    t_weights = time.perf_counter()

    # warm-up: both programs once, on prompts no cycle uses
    tok, cache = prefill(params, prompt_batch(
        generator.lm_prompts(traffic, vocab, seed, -1)))
    np.asarray(tok)
    t_prefill = time.perf_counter()
    tok, cache = step(params, tok, cache)
    np.asarray(tok)
    del tok, cache

    prof = Profile(trace)
    prof.start()
    served: List[Served] = []
    n_prompt = n_gen = 0
    t_start = time.perf_counter()
    setup_s = t_start - t0
    end = t_start + seconds
    with prof.span(Profile.WINDOW):
        cycle = 0
        cycle_s: List[float] = []
        while time.perf_counter() < end:
            t_cycle = time.perf_counter()
            cyc = Served(generator.lm_prompts(traffic, vocab, seed, cycle))
            served.append(cyc)
            with prof.span("bench.prefill"):
                tok, cache = prefill(params, prompt_batch(cyc.prompts))
                cyc.steps.append(np.asarray(tok)[:, 0])
            n_prompt += B * P
            n_gen += B
            for _ in range(G - 1):
                with prof.span("bench.decode_step"):
                    tok, cache = step(params, tok, cache)
                    cyc.steps.append(np.asarray(tok)[:, 0])
                n_gen += B
            del tok, cache
            cycle += 1
            cycle_s.append(time.perf_counter() - t_cycle)
    window_s = time.perf_counter() - t_start
    prof.stop()
    peak = harness.memory_peak_bytes(devs)
    del params

    picks = sample(served, G, int(traffic["check_sequences"]), seed)
    worst = widest_gap(weights, m, picks, served_gaps)
    n_steps = sum(len(c.steps) for c in served)

    layer: Dict[str, Any] = {"trace": prof.summary}
    if trace:
        pk = harness.peaks(devs[0].device_kind)
        work = 0.0
        for c in served:
            work += flops.prefill_flops(m, B, P)
            work += sum(flops.decode_step_flops(m, B, P + t)
                        for t in range(1, len(c.steps)))
        decode_steps = [(P + t) for c in served
                        for t in range(1, len(c.steps))]
        layer.update(
            peaks=pk, window_s=window_s, model_flops=work,
            decode_flops=[flops.decode_step_flops(m, B, ctx)
                          for ctx in decode_steps],
            decode_bytes=[flops.decode_step_bytes(m, B, ctx)
                          for ctx in decode_steps])
    return Outcome(
        attempted=len(served) * B,
        failed=0,
        e2e={"tokens_per_s": (n_prompt + n_gen) / window_s,
             "setup_s": setup_s},
        checks=[Check("served_token_gap", worst,
                      cell.limits["served_token_gap"])],
        memory_peak_bytes=peak,
        layer=layer,
        notes=[f"cycles {len(served)}, "
               f"steps {n_steps}, prompt tokens {n_prompt}, "
               f"generated tokens {n_gen}, window {window_s:.3f} s, "
               f"checked {len(picks)} sequences, "
               f"{sum(len(t) for _, t in picks)} served tokens",
               f"set-up: JAX start {t_enter - t0:.3f} s, weights "
               f"{t_weights - t_enter:.3f} s, prefill compiled or loaded and "
               f"run {t_prefill - t_weights:.3f} s, decode step "
               f"{t_start - t_prefill:.3f} s",
               "cycle wall s: " + " ".join(f"{c:.3f}" for c in cycle_s)],
    )
