"""Driver: a decoder LM served through ``ActorEngine.generate``.

Set-up makes the weights from the seed on the device (``bench.lib.lm_ref``,
in the layout the program takes, checked against the program's own
abstract layout), builds the engine, and generates one warm-up request
set, compiled in full.  Every call of the window generates a fresh request
set: the mix's fixed sizes and open-loop arrivals, with prompt tokens
drawn from the seed and the call's index, so no set repeats in a run.
The engine keeps one compiled program per live request count, so the
warm-up compiles the program that every call of the window uses, and each
call stages its requests into it; a call with a request count that the
engine keeps no program for would compile in the window, in full, since
the harness keeps the window's compiles out of the persistent cache.  The
check runs the plain float32 reference over a seeded sample of the
window's finished requests (the longest among them), prompt and served
tokens, and reads by how much each served token's reference logit lies
below the reference's best at that position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.lib import lm_ref, traffic

#: Finished requests of the window compared with the reference.
CHECKED_REQUESTS = 12
#: ``check`` arguments that put the control in the program's place: the
#: reference's linear layers in fp8, one precision below the bfloat16
#: weights.
CONTROL = {"quant": "fp8"}
# The smoke LM keeps the full model's depth and logit scale (init std x
# sqrt(width) = 0.02 x sqrt(3840)): the comparison's readings grow with
# depth, so at the published 24 layers the program, its control and a
# wrong token read about as they do at full width.
LM_SMOKE = dict(num_hidden_layers=24, hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                vocab_size=503, initializer_range=0.155)


def smoke(c: dict, mix: dict):
    """CPU test sizes: the smoke LM, 4 slots, 6 requests of short prompts
    and answers (a fixed prompt length fills the prompt window, as at full
    size)."""
    c = dict(c, **LM_SMOKE)
    c["serve"] = dict(c["serve"], batch_size=4, max_prompt=16, max_new=8)
    p = mix["prompt_len"]
    prompt = (dict(median=16, sigma=0.0, lo=16, hi=16) if p["lo"] == p["hi"]
              else dict(median=8, sigma=p["sigma"], lo=3, hi=16))
    return c, dict(mix, requests_per_call=6, prompt_len=prompt,
                   budget=dict(median=4, sigma=0.7, lo=2, hi=8))


def corrupt(monkeypatch):
    """Alter the last token of every served request where the engine
    produces it."""
    from repro.serve import ActorEngine
    real = ActorEngine.generate

    def generate(self, requests, **kw):
        out = real(self, requests, **kw)
        for r in out:
            r.tokens = r.tokens.copy()
            r.tokens[-1] = (r.tokens[-1] + self.cfg.vocab // 2) % self.cfg.vocab
        return out

    monkeypatch.setattr(ActorEngine, "generate", generate)


def arch_config(c: dict):
    """The program's model configuration from the configuration file."""
    from repro.configs.base import ArchConfig
    window = c["sliding_window"]
    return ArchConfig(
        name="bench", family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], head_dim=c["head_dim"],
        qkv_bias=c["attention_bias"], rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"], swa_window=window,
        attn_pattern=(1,) if window is None else (0,))


def check_layout(cfg, params) -> None:
    """The weights must have exactly the program's parameter layout."""
    from repro.models.lm import abstract_params
    want = jax.tree.map(lambda s: (s.shape, s.dtype), abstract_params(cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise ValueError("the program's parameter layout differs from "
                         "bench.lib.lm_ref.weight_shapes")


class Session:
    def __init__(self, cell, seed: int):
        from repro.serve import ActorEngine, ServeConfig
        c, mix = cell.config, cell.traffic
        self.conf = c
        self.arch = lm_ref.Arch.from_config(c)
        self.limit = c["limits"]["widest_logit_gap"]
        cfg = arch_config(c)
        self.params = lm_ref.make_weights(self.arch, traffic.jax_seed(seed))
        check_layout(cfg, self.params)
        s = c["serve"]
        self.engine = ActorEngine(cfg, self.params, ServeConfig(
            batch_size=s["batch_size"], max_prompt=s["max_prompt"],
            max_new=s["max_new"], eos_id=s["eos_id"]))
        self.sizes = traffic.RequestSizes.from_mix(mix)
        self.seed = seed
        self.sample_rng = np.random.default_rng([seed, 1])
        self.served = []        # (prompts, [served tokens or None]) per call
        # One warm-up set, compiled in full: a compile served from the
        # persistent cache leaves the compiler cold, and the window's first
        # call would pay the difference (about 5 s on a TPU v5e).
        with harness.compile_cache_off():
            self._generate(self.sizes.draw(self.arch.vocab,
                                           np.random.default_rng(
                                               [mix["sizes_seed"], 1])))

    def _generate(self, rs: traffic.RequestSet):
        from repro.serve import Request
        reqs = [Request(prompt=p, max_new=int(b))
                for p, b in zip(rs.prompts, rs.budgets)]
        with jax.profiler.TraceAnnotation("serve.generate"):
            return self.engine.generate(reqs, arrivals=rs.arrivals)

    def call(self) -> dict:
        rs = self.sizes.draw(self.arch.vocab, np.random.default_rng(
            [self.seed, 2, len(self.served)]))
        out = self._generate(rs)
        ok = [r.status == "ok" for r in out]
        self.served.append((rs.prompts, [r.tokens if o else None
                                         for r, o in zip(out, ok)]))
        return {"requests": len(out), "failed": ok.count(False),
                "tokens": sum(len(r.tokens) for r, o in zip(out, ok) if o),
                "sweeps": int(self.engine.last_sweeps),
                "prompt_lens": [len(p) for p in rs.prompts],
                "gen_lens": [len(r.tokens) for r in out]}

    def end_to_end(self, calls, window_s):
        return {"tokens_per_s": sum(r["tokens"] for r in calls) / window_s}

    def attempted_failed(self, calls):
        return (sum(r["requests"] for r in calls),
                sum(r["failed"] for r in calls))

    def observe(self, calls):
        return {"model": self.conf}

    def release(self):
        self.engine = None

    def sample(self):
        """(prompt, served tokens) of a seeded sample of the window's
        finished requests, the one with the most served tokens first."""
        done = [(p, t) for prompts, toks in self.served
                for p, t in zip(prompts, toks) if t is not None and len(t)]
        longest = max(range(len(done)), key=lambda k: len(done[k][1]))
        rest = [k for k in range(len(done)) if k != longest]
        pick = [longest] + list(self.sample_rng.permutation(rest)[
            :CHECKED_REQUESTS - 1])
        return [done[k] for k in pick]

    def check(self, calls, quant=None):
        """Widest gap, over the sampled requests' served tokens, between
        the reference's best logit and its logit of the served token.  With
        ``quant`` the reference in that precision takes the program's place
        and its first choice at each position is read instead: the
        control."""
        reqs = self.sample()
        seqs = [np.concatenate([p, t[:-1]]) for p, t in reqs]
        T = max(len(s) for s in seqs)
        tokens = np.zeros((len(seqs), T), np.int32)
        for r, s in enumerate(seqs):
            tokens[r, :len(s)] = s
        ref = lm_ref.logits(self.params, self.arch, tokens)
        rows, cols, served = [], [], []
        for r, (p, t) in enumerate(reqs):
            rows += [r] * len(t)
            cols += list(range(len(p) - 1, len(p) - 1 + len(t)))
            served += list(t)
        rows, cols = jnp.asarray(rows), jnp.asarray(cols)
        at = ref[rows, cols]                                  # (n, vocab)
        if quant is None:
            chosen = jnp.asarray(np.asarray(served, np.int32))
        else:
            alt = lm_ref.logits(self.params, self.arch, tokens, quant=quant)
            chosen = jnp.argmax(alt[rows, cols], axis=-1)
        gap = jnp.max(at, -1) - jnp.take_along_axis(at, chosen[:, None],
                                                    -1)[:, 0]
        return [("widest_logit_gap", float(jnp.max(gap)), self.limit)]
