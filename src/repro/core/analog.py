"""The analog linear layer — the paper's contribution as a composable JAX op.

``analog_linear`` is the single entry point every projection matmul in every
model routes through. Depending on :class:`AnalogConfig.mode` it executes:

* ``off``     — plain dense ``y = x @ w + b`` (the FP16/W16 reference path).
* ``analog``  — the full AIMC forward of the paper:
                eq. (1) static-input DAC quant (learnable range) →
                eq. (3) per-channel-max Gaussian weight-noise injection
                (training only; backward sees noise-free weights) →
                MVM →
                eq. (2) globally-static per-column ADC output quant (STE).
* ``qat``     — LLM-QAT baseline: static input quant + 4-bit per-channel
                weight fake-quant (STE), no noise, optional output quant.
* ``di8``     — dynamic per-token input quant (SpinQuant-DI8 baseline) +
                4-bit weight fake-quant.
* ``rtn``     — digital deployment: weights round-to-nearest quantized
                per-channel (Table 3 path); eval only.

Deployment-time *programming* noise (W_hw-noise) is applied once per model
instance by :func:`perturb_analog_weights` — not inside the forward — matching
the paper's protocol (10 seeds = 10 simulated chip programmings).

With :attr:`AnalogConfig.use_pallas` the ``analog``/``rtn`` MVMs execute as
one fused AIMC tile op on the Pallas kernels via ``repro.kernels.dispatch``
(DAC quant → MVM → per-column ADC quant; packed-int4 weights for ``rtn``
serving with :attr:`AnalogConfig.int4_serve`). The fused forward is
differentially tested against this file's unfused path
(``tests/test_kernel_dispatch.py``); training backward always uses the
unfused STE rules via the fused op's custom VJP.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import devices as devices_lib
from repro.core import noise as noise_lib
from repro.core import quant
from repro.kernels import dispatch
from repro.kernels import ref as kref


@dataclasses.dataclass(frozen=True)
class AnalogConfig:
    """Static configuration of the analog/quantized execution mode.

    Every field cites its origin in the paper (equation / section / table)
    so configs double as an experiment reference; see ``docs/noise.md`` for
    the noise model and ``docs/kernels.md`` for what changes when the
    fused kernels execute these semantics.

    Attributes:
        mode: Execution mode of every linear site — ``off`` (FP16/W16
            reference), ``analog`` (the paper's AIMC forward, §3.1),
            ``qat`` (LLM-QAT SI8-W4 baseline, §4 Table 1), ``di8``
            (SpinQuant-style dynamic-input-8-bit baseline, §4) or ``rtn``
            (round-to-nearest digital deployment, §4.3 Table 3).
        input_bits: DAC resolution of the eq. (1) static input quantizer
            (SI8 in the paper's SI8-W16-O8 recipe, §3.1).
        output_bits: ADC resolution of the eq. (2) per-column output
            quantizer (O8, §3.1).
        weight_bits: Weight quantization width for the ``qat`` / ``di8`` /
            ``rtn`` baselines (W4 in Tables 1 and 3; unused in ``analog``,
            which keeps W16 carriers and models hardware by noise).
        gamma_weight: Relative magnitude of the eq. (3) per-channel-max
            Gaussian weight noise injected during training (0.02 ≈ the
            Hermes PCM chip's observed programming error, §3.1).
        beta_mult: Multiplicative component of the eq. (5) combined noise
            model (App. C.2 ablation; 0 = purely additive eq. (3)).
        out_bound: λ_adc — the *globally static* bound of the eq. (2) ADC
            range, in units of (input range β × per-column weight max);
            12 for Phi-3, 14 for Llama (§3.1 / App. B).
        output_quant: O8 on/off (ablation Table 11: disabling output quant
            recovers a fraction of a point, hardware permitting).
        alpha_clip: Strength of the eq. (4) iterative weight clipping in
            units of the per-channel weight std (α = 3, §3.1).
        kappa_init: Multiplier on the EMA of input std used to initialize
            the learnable input ranges β (15 Phi-3, 18 Llama; App. B).
        init_steps: Length of the EMA-init phase in optimizer steps before
            β becomes a learned (LSQ-gradient) parameter (App. B).
        range_decay: Per-step multiplicative decay of β toward the live
            input absmax (the AIHWKIT-Lightning input-range learning rule,
            §2/App. B) — balances the LSQ counter-gradient.
        input_min_percentage: Floor on the decayed range as a fraction of
            the current absmax EMA (AIHWKIT-Lightning default 0.95).
        train_noise: Master switch for training-time noise injection
            (ablation App. C.2: no-noise HWA training loses robustness).
        use_pallas: Execute ``analog``/``rtn`` MVMs as one fused AIMC tile
            op (DAC → MVM → ADC) via the Pallas kernels — Mosaic on TPU,
            interpret-mode elsewhere; see ``docs/kernels.md``.
        int4_serve: With ``mode="rtn"`` + ``use_pallas``, serve weights
            from the packed-int4 kernel (two nibbles per byte, dequant in
            VMEM) — the Table 3 digital deployment at int4 weight
            bandwidth; pair with :func:`pack_int4_weights`.
        kv_bits: Serving-time KV-cache precision for the block-paged slot
            cache (``SchedulerConfig.paged``): 0 keeps the cache dtype as
            allocated, 8 stores K/V as int8 with per-token/head scales
            (``core.quant.kv_quantize``), quartering cache bytes vs fp32 —
            the same "analog-trained models tolerate low-precision digital
            inference" byproduct the paper demonstrates for weights (§4.3),
            applied to the decode memory wall. Eval/serve only.
        kv_splits: Split-K factor for the paged flash-decode kernel's
            2-pass reduction: the block loop is partitioned into this many
            independent partial reductions merged in a second pass — raise
            above 1 for long contexts where the decode batch alone can't
            fill the chip (kernel path; the CPU oracle ignores it).
    """

    mode: str = "off"                  # off | analog | qat | di8 | rtn
    input_bits: int = 8
    output_bits: int = 8
    weight_bits: int = 4               # qat / di8 / rtn modes
    gamma_weight: float = 0.02         # eq. (3) training-noise magnitude
    beta_mult: float = 0.0             # eq. (5) multiplicative component
    out_bound: float = 12.0            # lambda_adc (global; 12 Phi-3, 14 Llama)
    output_quant: bool = True          # O8 on/off (ablation Table 11)
    alpha_clip: float = 3.0            # eq. (4) clip strength
    kappa_init: float = 15.0           # EMA-init multiplier (15 Phi-3, 18 Llama)
    init_steps: int = 500              # EMA-init phase length
    range_decay: float = 0.01          # input-range decay (AIHWKIT-Lightning)
    input_min_percentage: float = 0.95
    train_noise: bool = True           # noise-injection on/off (ablation C.2)
    use_pallas: bool = False           # fused kernels (Mosaic on TPU,
                                       # interpret-mode elsewhere)
    int4_serve: bool = False           # rtn serving: packed-int4 weight kernel
    kv_bits: int = 0                   # paged KV cache: 0 = cache dtype, 8 = int8
    kv_splits: int = 1                 # paged flash-decode split-K factor

    @property
    def is_analog(self) -> bool:
        """True in the paper's AIMC execution mode."""
        return self.mode == "analog"

    @property
    def quantizes_input(self) -> bool:
        """True when the eq. (1) static input quantizer is active."""
        return self.mode in ("analog", "qat")


def _static_field(**kw):
    """Dataclass field marked static for jax.tree_util registration."""
    return dataclasses.field(metadata=dict(static=True), **kw)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AnalogCtx:
    """Per-call dynamic context threaded through the model."""

    key: Optional[jax.Array]           # rng for train-time noise (None at eval)
    training: bool = _static_field(default=False)
    collect_stats: bool = _static_field(default=False)


def empty_stats() -> dict:
    """Zero-valued per-site stats (fixed structure for lax.scan)."""
    return {"x_std": jnp.zeros((), jnp.float32),
            "x_absmax": jnp.zeros((), jnp.float32),
            "clip_frac": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# Matmul with noise-free backward (paper: "During the backward pass, the
# noise-free weights are used.")
# ---------------------------------------------------------------------------

@jax.custom_vjp
def noisy_matmul(x: jax.Array, w: jax.Array, w_noise: jax.Array) -> jax.Array:
    """``x @ (w + w_noise)`` forward; backward differentiates ``x @ w``."""
    return jnp.matmul(x, w + w_noise, preferred_element_type=jnp.float32)


def _noisy_matmul_fwd(x, w, w_noise):
    """custom_vjp forward: noisy product, save noise-free residuals."""
    y = jnp.matmul(x, w + w_noise, preferred_element_type=jnp.float32)
    return y, (x, w)


def _noisy_matmul_bwd(res, g):
    """custom_vjp backward: grads through the noise-free weights."""
    x, w = res
    in_dim, out_dim = w.shape[-2], w.shape[-1]
    g32 = g.astype(jnp.float32)
    dx = jnp.matmul(g32, w.astype(jnp.float32).T).astype(x.dtype)
    xm = x.reshape(-1, in_dim).astype(jnp.float32)
    gm = g32.reshape(-1, out_dim)
    dw = jnp.matmul(xm.T, gm).astype(w.dtype)
    return dx, dw, jnp.zeros_like(dw)


noisy_matmul.defvjp(_noisy_matmul_fwd, _noisy_matmul_bwd)


# ---------------------------------------------------------------------------
# Parameter construction / labeling
# ---------------------------------------------------------------------------

def init_linear(key: jax.Array, in_dim: int, out_dim: int, *, use_bias: bool,
                dtype=jnp.float32, scale: float | None = None) -> dict:
    """Initialize one analog-capable linear site.

    Besides ``kernel``/``bias`` it always carries ``input_range`` (the eq.-1
    learnable DAC range beta, shape ``(1,)``) so pytree structure is mode-
    independent (switching ``AnalogConfig.mode`` never reshapes checkpoints).
    """
    if scale is None:
        scale = in_dim ** -0.5
    p = {"kernel": (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
                    * scale).astype(dtype),
         "input_range": jnp.full((1,), 3.0, jnp.float32)}
    if use_bias:
        p["bias"] = jnp.zeros((out_dim,), dtype)
    return p


def linear_labels(p: dict) -> dict:
    """Label pytree for one linear site (drives clipping/optimizer policy)."""
    lab = {"kernel": "analog_weight", "input_range": "input_range"}
    if "bias" in p:
        lab["bias"] = "digital"
    return lab


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------

def analog_linear(p: dict, x: jax.Array, cfg: AnalogConfig,
                  ctx: AnalogCtx) -> tuple[jax.Array, dict]:
    """Apply one analog/quantized linear. Returns ``(y, stats)``.

    ``stats`` feeds the input-range EMA-init and decay rules applied by the
    trainer after each step (always returned with a fixed structure so it
    stacks cleanly under ``lax.scan`` over layers).
    """
    w = p["kernel"]
    in_dtype = x.dtype
    stats = empty_stats()

    if cfg.mode == "off":
        y = jnp.matmul(x, w.astype(in_dtype), preferred_element_type=jnp.float32)
        y = y.astype(in_dtype)
        if "bias" in p:
            y = y + p["bias"].astype(in_dtype)
        return y, stats

    # ---- input (DAC) side ----------------------------------------------
    fused = dispatch.use_fused(cfg)   # static: cfg is config, not a tracer
    if cfg.mode in ("analog", "qat", "rtn"):
        # Table-3 digital deployment is SI8-W4-O8: the RTN path reuses the
        # learned static input ranges and the global ADC output quantizer.
        beta = jnp.squeeze(p["input_range"]).astype(jnp.float32)
        xf = x.astype(jnp.float32)
        if ctx.collect_stats:
            stats = {
                "x_std": jax.lax.stop_gradient(jnp.std(xf)),
                "x_absmax": jax.lax.stop_gradient(jnp.max(jnp.abs(xf))),
                "clip_frac": jax.lax.stop_gradient(
                    jnp.mean((jnp.abs(xf) > beta).astype(jnp.float32))),
            }
        # The fused tile op quantizes inside the kernel; only the unfused
        # path (and the int4 digital periphery) quantizes here.
        x_q = None if fused else quant.input_quantize(xf, beta, cfg.input_bits)
    else:  # di8: dynamic per-token ranges (SpinQuant baseline)
        x_q = quant.dynamic_input_quantize(x.astype(jnp.float32), cfg.input_bits)
        beta = None
        fused = False

    # ---- weight side + MVM ------------------------------------------------
    wf = w.astype(jnp.float32)
    adc_done = False
    col_max = None                 # precomputed per-column absmax (int4 path)
    if cfg.mode == "analog":
        if ctx.training and cfg.train_noise and ctx.key is not None:
            w_noise = noise_lib.gaussian_weight_noise(
                ctx.key, wf, cfg.gamma_weight, cfg.beta_mult)
            w_noise = jax.lax.stop_gradient(w_noise)
        else:
            w_noise = jnp.zeros_like(wf)
        dev = p.get("device") if not ctx.training else None
        if dev is not None:
            # Per-tile device path (eval/serve only): drift/fault-corrupt
            # the weights once at this boundary so the fused kernel and
            # the unfused reference consume identical arrays. The ADC
            # bound stays calibrated on the *pristine* weights — hardware
            # ADC ranges are set at programming time and don't track
            # drift (core.devices.corrupt_weights).
            bound = jax.lax.stop_gradient(
                kref.adc_bound(wf, beta, cfg.out_bound))
            w_dev, col_off = devices_lib.corrupt_weights(wf, dev, bound)
            w_dev = jax.lax.stop_gradient(w_dev)
            col_off = jax.lax.stop_gradient(col_off)
            if fused:
                y = dispatch.analog_mvm(
                    xf, w_dev + w_noise, beta, bound,
                    in_bits=cfg.input_bits, out_bits=cfg.output_bits,
                    col_off=col_off)
            else:
                if x_q is None:
                    x_q = quant.input_quantize(xf, beta, cfg.input_bits)
                y = noisy_matmul(x_q, w_dev, w_noise) + col_off
                if cfg.output_quant:
                    y = quant.output_quantize(
                        y, bound, jnp.float32(cfg.output_bits))
            adc_done = True
        elif fused:
            bound = jax.lax.stop_gradient(
                kref.adc_bound(wf, beta, cfg.out_bound))
            y = dispatch.fused_analog_mvm(
                xf, wf, w_noise, beta, bound,
                in_bits=cfg.input_bits, out_bits=cfg.output_bits)
            adc_done = True
        else:
            y = noisy_matmul(x_q, wf, w_noise)
    elif cfg.mode in ("qat", "di8"):
        w_q = quant.weight_fake_quant(wf, cfg.weight_bits)
        y = jnp.matmul(x_q, w_q, preferred_element_type=jnp.float32)
    else:  # rtn (eval-only: no autodiff rules needed on the fused paths)
        use_int4 = (cfg.use_pallas and cfg.int4_serve
                    and dispatch.can_use_int4(w.shape[-1], cfg.weight_bits))
        if use_int4:
            # Packed-int4 serving kernel; DAC/ADC quantization stay in the
            # digital periphery (same bound as unfused). Independent of
            # output_quant — the ADC is outside this kernel.
            if x_q is None:
                x_q = quant.input_quantize(xf, beta, cfg.input_bits)
            if "int4" in p:   # precomputed once by pack_int4_weights
                y = dispatch.int4_mvm_packed(
                    x_q, p["int4"]["packed"], p["int4"]["scale"])
                col_max = p["int4"]["colmax"]
            else:             # functional fallback: quantize+pack per call
                w_int, scale = quant.rtn_quantize(wf, cfg.weight_bits)
                wf = quant.rtn_dequantize(w_int, scale)
                y = dispatch.int4_mvm(x_q, w_int, scale)
        else:
            w_int, scale = quant.rtn_quantize(wf, cfg.weight_bits)
            wf = quant.rtn_dequantize(w_int, scale)
            if fused:
                bound = jax.lax.stop_gradient(
                    kref.adc_bound(wf, beta, cfg.out_bound))
                y = dispatch.analog_mvm(xf, wf, beta, bound,
                                        in_bits=cfg.input_bits,
                                        out_bits=cfg.output_bits)
                adc_done = True
            else:
                y = jnp.matmul(x_q, wf, preferred_element_type=jnp.float32)

    # ---- output (ADC) side -----------------------------------------------
    if (cfg.output_quant and cfg.mode in ("analog", "rtn")
            and beta is not None and not adc_done):
        if col_max is not None:   # precomputed dequantized-weight absmax
            bound = jax.lax.stop_gradient(cfg.out_bound * beta * col_max)
        else:
            bound = jax.lax.stop_gradient(
                kref.adc_bound(wf, beta, cfg.out_bound))
        y = quant.output_quantize(y, bound, jnp.float32(cfg.output_bits))

    y = y.astype(in_dtype)
    if "bias" in p:  # bias added in the digital periphery (FP16)
        y = y + p["bias"].astype(in_dtype)
    return y, stats


# ---------------------------------------------------------------------------
# Deployment-time weight perturbation (programming noise / Fig. 3 sweeps)
# ---------------------------------------------------------------------------

def perturb_analog_weights(params, labels, key: jax.Array, model: str,
                           gamma: float = 0.0):
    """Simulate one chip programming: perturb every analog weight once.

    ``model``: ``"hw"`` (PCM Hermes polynomial) or ``"gaussian"`` (Fig.-3
    sweep at relative magnitude ``gamma``) or ``"none"``.
    """
    if model == "none":
        return params
    leaves, treedef = jax.tree_util.tree_flatten(params)
    lab_leaves = jax.tree_util.tree_leaves(labels)
    assert len(leaves) == len(lab_leaves)
    out = [_perturb_leaf(leaf, jax.random.fold_in(key, i), model, gamma)
           if lab == "analog_weight" else leaf
           for i, (leaf, lab) in enumerate(zip(leaves, lab_leaves))]
    return jax.tree_util.tree_unflatten(treedef, out)


@functools.partial(jax.jit, static_argnames=("model", "gamma"))
def _perturb_leaf(leaf, key, model: str, gamma: float):
    """One analog weight leaf's perturbation, one key per stacked layer.

    Jitted so the elementwise noise chain fuses: run op by op, a
    full-width leaf (llama-3.2-1b's stacked gate_up is 2 GiB in f32)
    materializes several leaf-sized temporaries and overflows one chip.
    """
    # stacked scan weights [L, in, out]: channel axis is -2 regardless
    flat = leaf.reshape((-1,) + leaf.shape[-2:])
    ks = jax.random.split(key, flat.shape[0])
    pert = jax.vmap(
        lambda w, kk: noise_lib.apply_eval_noise(kk, w, model, gamma)
    )(flat, ks)
    return pert.reshape(leaf.shape)


def sample_noise_instances(params, labels, key: jax.Array, model: str):
    """Sample one deployment's *unit* noise instance per analog weight.

    One chip programming = one sampled noise instance, reused across every
    eval batch (and, for the gaussian model, across every ``gamma`` sweep
    point — the instance is a *unit* perturbation that
    :func:`apply_noise_instances` scales by ``gamma``). Re-sampling per
    eval call would change the experiment the paper specifies: Fig. 3
    compares the *same* simulated chip at different noise magnitudes. Key
    folding matches :func:`perturb_analog_weights` (same per-leaf and
    per-layer keys). Returns a pytree shaped like ``params`` with zero
    leaves at non-analog sites.
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    lab_leaves = jax.tree_util.tree_leaves(labels)
    assert len(leaves) == len(lab_leaves)
    out = []
    for i, (leaf, lab) in enumerate(zip(leaves, lab_leaves)):
        if lab == "analog_weight" and model != "none":
            k = jax.random.fold_in(key, i)
            flat = leaf.reshape((-1,) + leaf.shape[-2:])
            ks = jax.random.split(k, flat.shape[0])
            inst = jax.vmap(
                lambda w, kk: noise_lib.sample_noise_instance(kk, w, model)
            )(flat, ks)
            out.append(inst.reshape(leaf.shape))
        else:
            out.append(jnp.zeros_like(leaf))
    return jax.tree_util.tree_unflatten(treedef, out)


def apply_noise_instances(params, labels, instances, model: str,
                          gamma: float = 0.0):
    """Perturb analog weights with a pre-sampled deployment noise instance.

    ``instances`` comes from :func:`sample_noise_instances` (same params /
    labels). ``"hw"`` instances are absolute perturbations (``w + inst``);
    ``"gaussian"`` instances are unit perturbations scaled by ``gamma``
    (``w + gamma * inst``) — so a gamma sweep over one instance tree
    compares the same simulated chip throughout. The same honest-config
    rules as ``core.noise.apply_eval_noise`` apply.
    """
    if model == "none":
        return params
    noise_lib.validate_noise_config(model, gamma)
    scale = gamma if model == "gaussian" else 1.0
    leaves, treedef = jax.tree_util.tree_flatten(params)
    lab_leaves = jax.tree_util.tree_leaves(labels)
    inst_leaves = jax.tree_util.tree_leaves(instances)
    assert len(leaves) == len(lab_leaves) == len(inst_leaves)
    out = [leaf + scale * inst if lab == "analog_weight" else leaf
           for leaf, lab, inst in zip(leaves, lab_leaves, inst_leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def pack_int4_weights(params, labels=None, bits: int = 4):
    """Serving-side transform: precompute the packed-int4 carriers.

    Walks every analog linear site and attaches an ``"int4"`` sub-dict —
    ``packed`` [.., K, ceil(N/256)*128] uint8 two-nibble weights in the
    ``kernels.ref.pack_int4`` layout, ``scale`` [.., N]
    per-column dequant scales, ``colmax`` [.., N] per-column absmax of the
    *dequantized* weights (so the runtime ADC bound matches the unfused RTN
    path bit-for-bit). ``analog_linear``'s ``int4_serve`` path consumes
    these directly, so serving never re-quantizes or re-packs per call and
    decode reads weights at int4 bandwidth. Sites with odd N (unpackable)
    are left untouched and fall back to on-the-fly packing.

    With ``labels=None`` the analog sites are detected structurally: a dict
    holding both ``"kernel"`` and ``"input_range"`` is an analog linear
    (digital linears like the MoE router carry a bare kernel and are
    skipped). This serves pytrees whose label tree is unavailable — e.g.
    the scheduler's layer-truncated drafter params, where slicing the
    stacked blocks would otherwise require slicing the labels in lockstep.

    Stacked scan weights [L, K, N] keep their leading dims (packed arrays
    stack the same way, so ``lax.scan`` slices them per layer as usual).
    Training pytrees are untouched — this is an opt-in deployment transform,
    like :func:`quantize_for_digital`.
    """
    def walk(p, lab):
        if not isinstance(p, dict):
            return p
        out = {k: walk(p[k], lab[k] if lab is not None else None) for k in p}
        if lab is not None:
            is_site = (isinstance(lab, dict)
                       and lab.get("kernel") == "analog_weight")
        else:
            is_site = "kernel" in p and "input_range" in p
        if is_site and p["kernel"].shape[-1] % 2 == 0:
            out["int4"] = _pack_int4_site(p["kernel"], bits)
        return out

    return walk(params, labels)


@functools.partial(jax.jit, static_argnames=("bits",))
def _pack_int4_site(w, bits: int):
    """One site's int4 carriers (see :func:`pack_int4_weights`). Jitted
    so the quantize/pack chain fuses: run op by op, a full-width leaf
    materializes several leaf-sized temporaries and overflows one chip."""
    flat = w.reshape((-1,) + w.shape[-2:])

    def one(wk):
        w_int, scale = quant.rtn_quantize(wk.astype(jnp.float32), bits)
        deq = quant.rtn_dequantize(w_int, scale)
        return (kref.pack_int4(w_int), scale[0],
                jnp.max(jnp.abs(deq), axis=0))

    packed, scale, colmax = jax.vmap(one)(flat)
    lead = w.shape[:-2]
    return {"packed": packed.reshape(lead + packed.shape[1:]),
            "scale": scale.reshape(lead + scale.shape[1:]),
            "colmax": colmax.reshape(lead + colmax.shape[1:])}


def quantize_for_digital(params, labels, bits: int = 4):
    """Table-3 path: RTN-quantize every analog weight in place (dequantized
    float carrier; the packed-int4 kernel consumes ``rtn_quantize`` output
    directly on the serving path)."""
    def _q(label, p):
        if label == "analog_weight":
            flat = p.reshape((-1,) + p.shape[-2:])
            w_int, scale = jax.vmap(
                lambda w: quant.rtn_quantize(w, bits))(flat)
            deq = jax.vmap(quant.rtn_dequantize)(w_int, scale)
            return deq.reshape(p.shape).astype(p.dtype)
        return p

    return jax.tree_util.tree_map(_q, labels, params)
