"""A configuration file as the benchmark reads it, and its weights.

``Spec`` holds the sizes of ``configs/<name>.json`` under the published
keys, and the deployment stated there. ``make_params`` draws the weights
from the seed, on the device, in one jitted call, in the layout the
program serves (layer-stacked, fused qkv and gate_up) and in float32.
For the ``pcm_hermes`` deployment the same call also programs the chip:
each analog weight gets one draw of the Hermes PCM programming noise
(paper App. E.3), so the benchmark, and not the program, holds the
programmed weights that its plain reference is computed with.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

CONFIG_DIR = pathlib.Path(__file__).resolve().parent / "configs"

#: Hermes PCM programming error (paper App. E.3): sigma in percent of the
#: per-column max weight, as a cubic in the weight's magnitude in percent
#: of that max. An exact zero is noiseless.
PCM_COEFFS = (1.23e-5, -3.06e-3, 2.45e-1, 2.11)

#: DAC range of the LM head's input. The head's outputs are logits; at the
#: inner sites' range of 3 the 8-bit ADC clips the top logits of random
#: weights (bound 12 * 3 * colmax is about 2.4 standard deviations), and
#: the argmax would be decided by the clip. At 8 the bound is about 6.3.
HEAD_INPUT_RANGE = 8.0


@dataclasses.dataclass(frozen=True)
class Spec:
    """Sizes and deployment of one configuration (hashable: a jit key)."""

    name: str
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool
    mode: str                  # "analog" | "rtn"
    noise: str                 # "pcm_hermes" | "none"
    weight_bits: int
    input_bits: int
    output_bits: int
    out_bound: float
    input_range: float
    arch: str                  # the program's architecture name
    deploy: str                # the program's deployment name

    @property
    def padded_vocab(self) -> int:
        """Rows of the embedding and columns of the head: the vocabulary
        rounded up to a multiple of 256, as the program holds them."""
        return -(-self.vocab // 256) * 256

    @property
    def qkv_out(self) -> int:
        """Output width of the fused q|k|v projection."""
        return (self.heads + 2 * self.kv_heads) * self.head_dim


def load_config(name: str, root: pathlib.Path = CONFIG_DIR) -> dict:
    """The JSON of configuration ``name`` (``configs/<name>.json``)."""
    path = root / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} at {path}")
    return json.loads(path.read_text())


def spec_of(conf: dict) -> Spec:
    """The ``Spec`` a configuration file states."""
    if conf["hidden_act"] != "silu":
        raise ValueError(f"{conf['name']}: only SwiGLU MLPs are served")
    dep = conf["deployment"]
    heads = conf["num_attention_heads"]
    return Spec(
        name=conf["name"], layers=conf["num_hidden_layers"],
        d=conf["hidden_size"], heads=heads,
        kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim", conf["hidden_size"] // heads),
        ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        eps=float(conf["rms_norm_eps"]),
        tied=bool(conf["tie_word_embeddings"]), mode=dep["mode"],
        noise=dep["programming_noise"],
        weight_bits=int(dep.get("weight_bits", 16)),
        input_bits=int(dep["input_bits"]),
        output_bits=int(dep["output_bits"]),
        out_bound=float(dep["out_bound"]),
        input_range=float(dep["input_range"]),
        arch=conf["program"]["arch"], deploy=conf["program"]["deploy"])


def key_of(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (folded to 32 bits)."""
    return jax.random.PRNGKey(int(np.random.default_rng(seed)
                                  .integers(2 ** 31)))


def pcm_program(key, w):
    """``w`` plus one draw of Hermes PCM programming noise; the channel
    is the output column (max over the input axis, ``-2``)."""
    wmax = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-12)
    pct = 100.0 * jnp.abs(w) / wmax
    a3, a2, a1, a0 = PCM_COEFFS
    sigma = (((a3 * pct + a2) * pct + a1) * pct + a0) / 100.0 * wmax
    tau = jax.random.normal(key, w.shape, jnp.float32)
    return w + jnp.where(w == 0, 0.0, sigma * tau)


@functools.partial(jax.jit, static_argnames=("spec",))
def make_params(key, spec: Spec) -> dict:
    """All weights of ``spec`` from ``key``, in the program's layout."""
    counter = iter(range(1 << 20))

    def normal(shape, fan_in):
        k = jax.random.fold_in(key, next(counter))
        return jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5

    def site(shape, fan_in, beta):
        w = normal(shape, fan_in)
        if spec.noise == "pcm_hermes":
            w = pcm_program(jax.random.fold_in(key, next(counter)), w)
        lead = shape[:-2]
        return {"kernel": w,
                "input_range": jnp.full(lead + (1,), beta, jnp.float32)}

    L, d, beta = spec.layers, spec.d, spec.input_range
    ones = lambda *s: jnp.ones(s, jnp.float32)
    params = {
        "embed": {"tokens": normal((spec.padded_vocab, d), d)},
        "blocks": {
            "ln1": {"scale": ones(L, d)},
            "attn": {"qkv": site((L, d, spec.qkv_out), d, beta),
                     "o": site((L, spec.heads * spec.head_dim, d),
                               spec.heads * spec.head_dim, beta)},
            "ln2": {"scale": ones(L, d)},
            "ffn": {"gate_up": site((L, d, 2 * spec.ff), d, beta),
                    "down": site((L, spec.ff, d), spec.ff, beta)},
        },
        "final_norm": {"scale": ones(d)},
    }
    if not spec.tied:
        params["lm_head"] = site((d, spec.padded_vocab), d, HEAD_INPUT_RANGE)
    return params
