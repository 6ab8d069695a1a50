"""Samplers as ``lax.scan`` loops in sigma space — in resumable form.

A sampler advances ``x`` down a sigma ladder using a *denoiser*
``denoise(x, sigma) -> x0_hat``. The denoiser hides the model
parameterization (eps-pred UNet, flow DiT) and any guidance — see
``guidance.py`` and ``pipeline.py``.

All samplers are data-dependent-control-flow-free: fixed step count, fixed
shapes, stochastic steps derive per-step keys with ``fold_in`` — so a whole
sampling run compiles to a single XLA while/scan and never returns to the
host between steps (the reference pays a Python round-trip per *tile* per
step through ComfyUI's sampler; SURVEY §3.3 "GPU HOT LOOP").

Since ISSUE 14 every sampler is expressed as a :class:`SamplerProgram` —
an explicit ``(init, step, extract)`` triple over a pytree *carry* — so
the scan can be cut at ANY step boundary: :func:`run_segment` runs steps
``[start, start+length)`` and returns the carry, which (with the step
cursor) is the complete sampler state. That is what makes step-granular
preemption exact (``diffusion/checkpoint.py``): a run split into
segments, round-tripped through host numpy between them, is bit-identical
to the monolithic scan because each step applies the SAME step closure to
the SAME carry values at the SAME global index ``i`` — stochastic
samplers included, since their per-step noise is ``fold_in(key, i)`` of
the global index, never of a per-segment counter.

Carry contract (relied on by the sharded preemptible pipeline): every
leaf is either *state-shaped* (same shape as ``x`` — latents and D/x0
history slots) or a rank-0 scalar derived only from ``(sigmas, step
index)`` (step-count flags, h-history) — scalars are therefore identical
across dp shards and may be carried replicated.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..telemetry.device_scopes import device_scope
from .progress import sampler_step

Denoiser = Callable[[jax.Array, jax.Array], jax.Array]   # (x, sigma[]) -> x0_hat


@dataclasses.dataclass(frozen=True)
class SamplerProgram:
    """One sampler bound to ``(denoise, sigmas, key, kwargs)``.

    ``init(x) -> carry`` builds the scan carry (a tuple of arrays; slot 0
    is always the evolving latent unless ``extract`` says otherwise);
    ``step(carry, i) -> carry`` advances one GLOBAL ladder index;
    ``extract(carry) -> x0`` picks the output slot after the final step.
    ``init`` and ``extract`` are pure structure — they never call the
    denoiser — so carry shapes can be derived abstractly
    (``jax.eval_shape``) and the output extracted without rebuilding the
    model closure."""

    name: str
    n_steps: int
    init: Callable[[jax.Array], tuple]
    step: Callable[[tuple, jax.Array], tuple]
    extract: Callable[[tuple], jax.Array]


def _scan_body(prog: SamplerProgram, tap=None):
    """The scan body of both drivers: one step at its GLOBAL index, which
    a progress-streaming denoiser reads (``progress.sampler_step``) to
    report only every stride-th step. With a ``tap`` (a
    ``progress.DenoiserTap`` the program's denoiser was wrapped in) the
    step's per-step output is what the tap kept of it, ``(sigma,
    x0[:1])``; without one there is no output and nothing is added."""

    def body(carry, i):
        with sampler_step(i):
            if tap is None:
                return prog.step(carry, i), None
            tap.take()
            return prog.step(carry, i), tap.take()

    return body


def run_segment(prog: SamplerProgram, carry: tuple, start, length: int,
                tap=None):
    """Advance ``length`` steps from global index ``start``.

    ``start`` may be traced (one compiled segment program serves every
    offset of that length); ``length`` is static. The xs are
    ``start + arange(length)`` so the step closure sees the same global
    indices the monolithic scan would. With a ``tap`` the answer is
    ``(carry, (sigma, x0))`` of the segment's last step (so ``length`` is
    1 or more) — progress as an ordinary output, the carry's bits
    untouched."""
    if length <= 0:
        return carry
    with device_scope("sampler"):
        xs = jnp.asarray(start, jnp.int32) + jnp.arange(length,
                                                        dtype=jnp.int32)
    carry, seen = jax.lax.scan(_scan_body(prog, tap), carry, xs)
    if tap is None:
        return carry
    with device_scope("sampler"):
        return carry, jax.tree.map(lambda rows: rows[-1], seen)


def equal_segment_steps(n_steps: int, at_most: int) -> int:
    """The length to cut an ``n_steps`` ladder at so that it runs in the
    fewest segments of ``at_most`` steps or fewer, all equal but for a
    shorter last one: 28 at most 8 at a time is 7+7+7+7 — ONE compiled
    segment program where 8+8+8+4 would be two."""
    n_segments = max(1, -(-n_steps // max(1, at_most)))
    return -(-n_steps // n_segments)


def run_program(prog: SamplerProgram, x: jax.Array) -> jax.Array:
    """The monolithic run: init → scan the whole ladder → extract."""
    carry = prog.init(x)
    carry, _ = jax.lax.scan(_scan_body(prog), carry,
                            jnp.arange(prog.n_steps, dtype=jnp.int32))
    return prog.extract(carry)


def _extract_first(carry: tuple) -> jax.Array:
    return carry[0]


def _to_d(x: jax.Array, sigma: jax.Array, denoised: jax.Array) -> jax.Array:
    """Convert x0 prediction to the k-diffusion ODE derivative."""
    return (x - denoised) / jnp.maximum(sigma, 1e-10)


def _ancestral_sigmas(sigma_from, sigma_to, eta):
    """Split a σ_from→σ_to transition into a deterministic step plus an
    ancestral noise injection (k-diffusion ``get_ancestral_step``)."""
    var_ratio = jnp.maximum(
        1.0 - (sigma_to / jnp.maximum(sigma_from, 1e-10)) ** 2, 0.0)
    sigma_up = jnp.minimum(sigma_to, eta * sigma_to * jnp.sqrt(var_ratio))
    sigma_down = jnp.sqrt(jnp.maximum(sigma_to ** 2 - sigma_up ** 2, 0.0))
    return sigma_down, sigma_up


def _t_of(sigma):
    """log-SNR time t = −log σ (the exponential-integrator clock all the
    multistep solvers below share)."""
    return -jnp.log(jnp.maximum(sigma, 1e-10))


def _i0(h):
    """∫₀ʰ e^{τ−h} dτ = 1 − e^{−h} — weight of a constant D over one
    exponential-integrator step."""
    return -jnp.expm1(-h)


# --- program builders -------------------------------------------------------


def _euler_program(denoise, sigmas, key=None) -> SamplerProgram:
    del key

    def step(carry, i):
        (x,) = carry
        with device_scope("sampler"):
            sigma, sigma_next = sigmas[i], sigmas[i + 1]
        denoised = denoise(x, sigma)
        with device_scope("sampler"):
            d = _to_d(x, sigma, denoised)
            return (x + d * (sigma_next - sigma),)

    return SamplerProgram("euler", sigmas.shape[0] - 1,
                          lambda x: (x,), step, _extract_first)


def _euler_ancestral_program(denoise, sigmas, key,
                             eta: float = 1.0) -> SamplerProgram:
    def step(carry, i):
        (x,) = carry
        with device_scope("sampler"):
            sigma, sigma_next = sigmas[i], sigmas[i + 1]
        denoised = denoise(x, sigma)
        with device_scope("sampler"):
            sigma_down, sigma_up = _ancestral_sigmas(sigma, sigma_next, eta)
            d = _to_d(x, sigma, denoised)
            x = x + d * (sigma_down - sigma)
            noise = jax.random.normal(jax.random.fold_in(key, i), x.shape,
                                      x.dtype)
            # last step has sigma_next == 0 → sigma_up == 0 → no noise added
            return (x + noise * sigma_up,)

    return SamplerProgram("euler_ancestral", sigmas.shape[0] - 1,
                          lambda x: (x,), step, _extract_first)


def _heun_program(denoise, sigmas, key=None) -> SamplerProgram:
    del key

    def step(carry, i):
        (x,) = carry
        with device_scope("sampler"):
            sigma, sigma_next = sigmas[i], sigmas[i + 1]
        denoised = denoise(x, sigma)
        with device_scope("sampler"):
            d = _to_d(x, sigma, denoised)
            dt = sigma_next - sigma
            x_euler = x + d * dt

        # the branch calls the model: the cond stays outside the scope, the
        # branch's own arithmetic opens it
        def heun_correct(_):
            denoised2 = denoise(x_euler, sigma_next)
            with device_scope("sampler"):
                d2 = _to_d(x_euler, sigma_next, denoised2)
                return x + (d + d2) / 2 * dt

        # at the final step sigma_next==0: plain euler (no second eval at σ=0)
        x = jax.lax.cond(sigma_next > 0, heun_correct, lambda _: x_euler, None)
        return (x,)

    return SamplerProgram("heun", sigmas.shape[0] - 1,
                          lambda x: (x,), step, _extract_first)


def _dpmpp_2m_program(denoise, sigmas, key=None) -> SamplerProgram:
    """DPM-Solver++(2M): second-order multistep on log-sigma."""
    del key

    def t_of(sigma):
        return -jnp.log(jnp.maximum(sigma, 1e-10))

    def step(carry, i):
        x, old_denoised, have_old = carry
        with device_scope("sampler"):
            sigma, sigma_next = sigmas[i], sigmas[i + 1]
        denoised = denoise(x, sigma)
        with device_scope("sampler"):

            def first_order(_):
                # exact Euler in exponential-integrator form
                return x * (sigma_next / sigma) + denoised * (1 - sigma_next / sigma)

            def second_order(_):
                h = t_of(sigma_next) - t_of(sigma)
                h_last = t_of(sigma) - t_of(sigmas[i - 1])
                r = h_last / jnp.maximum(h, 1e-10)
                denoised_d = (1 + 1 / (2 * r)) * denoised - (1 / (2 * r)) * old_denoised
                return x * (sigma_next / sigma) + denoised_d * (1 - sigma_next / sigma)

            use_second = jnp.logical_and(have_old, sigma_next > 0)
            x_new = jax.lax.cond(use_second, second_order, first_order, None)
            # sigma_next == 0: x -> denoised exactly
            x_new = jnp.where(sigma_next > 0, x_new, denoised)
            return (x_new, denoised, jnp.array(True))

    return SamplerProgram(
        "dpmpp_2m", sigmas.shape[0] - 1,
        lambda x: (x, jnp.zeros_like(x), jnp.array(False)),
        step, _extract_first)


def _ddim_program(denoise, sigmas, key=None,
                  eta: float = 0.0) -> SamplerProgram:
    """DDIM in sigma space. ``eta=0`` is the deterministic solver (the
    x0-form of Euler); ``eta>0`` interpolates toward ancestral sampling."""

    def step(carry, i):
        (x,) = carry
        with device_scope("sampler"):
            sigma, sigma_next = sigmas[i], sigmas[i + 1]
        denoised = denoise(x, sigma)
        with device_scope("sampler"):
            if eta and key is not None:
                sigma_down, sigma_up = _ancestral_sigmas(sigma, sigma_next, eta)
            else:
                sigma_down, sigma_up = sigma_next, jnp.zeros(())
            x = denoised + (x - denoised) * (sigma_down / jnp.maximum(sigma, 1e-10))
            if eta and key is not None:
                noise = jax.random.normal(jax.random.fold_in(key, i),
                                          x.shape, x.dtype)
                x = x + noise * sigma_up
            return (x,)

    return SamplerProgram("ddim", sigmas.shape[0] - 1,
                          lambda x: (x,), step, _extract_first)


def _lcm_program(denoise, sigmas, key) -> SamplerProgram:
    """Latent-consistency sampling: jump to x0, re-noise to the next
    sigma (k-diffusion ``sample_lcm``)."""

    def step(carry, i):
        (x,) = carry
        with device_scope("sampler"):
            sigma, sigma_next = sigmas[i], sigmas[i + 1]
        denoised = denoise(x, sigma)
        with device_scope("sampler"):
            noise = jax.random.normal(jax.random.fold_in(key, i),
                                      x.shape, x.dtype)
            return (denoised + jnp.where(sigma_next > 0, sigma_next, 0.0) * noise,)

    return SamplerProgram("lcm", sigmas.shape[0] - 1,
                          lambda x: (x,), step, _extract_first)


def _dpmpp_sde_program(denoise, sigmas, key, eta: float = 1.0,
                       s_noise: float = 1.0,
                       r: float = 0.5) -> SamplerProgram:
    """DPM-Solver++ (SDE): single-step second-order with an ancestral
    noise injection at the midpoint and endpoint (k-diffusion
    ``sample_dpmpp_sde``)."""

    def t_of(sigma):
        return -jnp.log(jnp.maximum(sigma, 1e-10))

    def sigma_of(t):
        return jnp.exp(-t)

    def step(carry, i):
        (x,) = carry
        with device_scope("sampler"):
            sigma, sigma_next = sigmas[i], sigmas[i + 1]
        denoised = denoise(x, sigma)

        def last(_):
            return denoised

        def stage(_):
            with device_scope("sampler"):
                t, t_next = t_of(sigma), t_of(sigma_next)
                h = t_next - t
                s = t + h * r
                fac = 1.0 / (2.0 * r)
                # midpoint stage with its own ancestral split
                sd1, su1 = _ancestral_sigmas(sigma_of(t), sigma_of(s), eta)
                s_down = t_of(sd1)
                x2 = (sigma_of(s_down) / sigma_of(t)) * x \
                    - jnp.expm1(t - s_down) * denoised
                noise1 = jax.random.normal(jax.random.fold_in(key, 2 * i),
                                           x.shape, x.dtype)
                x2 = x2 + noise1 * su1 * s_noise
                sigma_s = sigma_of(s)
            denoised2 = denoise(x2, sigma_s)
            with device_scope("sampler"):
                # full step
                sd2, su2 = _ancestral_sigmas(sigma_of(t), sigma_of(t_next),
                                             eta)
                t_down = t_of(sd2)
                denoised_d = (1 - fac) * denoised + fac * denoised2
                x_new = (sigma_of(t_down) / sigma_of(t)) * x \
                    - jnp.expm1(t - t_down) * denoised_d
                noise2 = jax.random.normal(
                    jax.random.fold_in(key, 2 * i + 1), x.shape, x.dtype)
                return x_new + noise2 * su2 * s_noise

        return (jax.lax.cond(sigma_next > 0, stage, last, None),)

    return SamplerProgram("dpmpp_sde", sigmas.shape[0] - 1,
                          lambda x: (x,), step, _extract_first)


def _dpmpp_2m_sde_program(denoise, sigmas, key, eta: float = 1.0,
                          s_noise: float = 1.0) -> SamplerProgram:
    """DPM-Solver++(2M) SDE, midpoint solver (k-diffusion
    ``sample_dpmpp_2m_sde``)."""

    def t_of(sigma):
        return -jnp.log(jnp.maximum(sigma, 1e-10))

    def step(carry, i):
        x, old_denoised, h_last, have_old = carry
        with device_scope("sampler"):
            sigma, sigma_next = sigmas[i], sigmas[i + 1]
        denoised = denoise(x, sigma)
        with device_scope("sampler"):

            def last(_):
                return denoised, jnp.zeros(())

            def stage(_):
                h = t_of(sigma_next) - t_of(sigma)
                eta_h = eta * h
                x_new = (sigma_next / jnp.maximum(sigma, 1e-10)) \
                    * jnp.exp(-eta_h) * x \
                    - jnp.expm1(-h - eta_h) * denoised
                r = h_last / jnp.maximum(h, 1e-10)
                second = -jnp.expm1(-h - eta_h) * (0.5 / jnp.maximum(r, 1e-10)) \
                    * (denoised - old_denoised)
                x_new = x_new + jnp.where(have_old, second, 0.0)
                noise = jax.random.normal(jax.random.fold_in(key, i),
                                          x.shape, x.dtype)
                x_new = x_new + noise * sigma_next * s_noise \
                    * jnp.sqrt(jnp.maximum(-jnp.expm1(-2.0 * eta_h), 0.0))
                return x_new, h

            x_new, h = jax.lax.cond(sigma_next > 0, stage, last, None)
            return (x_new, denoised, h, jnp.array(True))

    return SamplerProgram(
        "dpmpp_2m_sde", sigmas.shape[0] - 1,
        lambda x: (x, jnp.zeros_like(x), jnp.zeros(()), jnp.array(False)),
        step, _extract_first)


def _res_2m_program(denoise, sigmas, key=None,
                    eta: float = 0.0) -> SamplerProgram:
    """RES second-order multistep (the RES4LYF-family ``res_2m``):
    exponential Adams–Bashforth on the data prediction.

    Exact variation-of-constants: with t = −log σ the probability-flow
    ODE is dx/dt + x = D(x), so
    ``x_{n+1} = e^{−h} x_n + ∫₀ʰ e^{τ−h} D(t_n+τ) dτ``. Approximating D
    linearly through (t_{n−1}, D_{n−1}), (t_n, D_n) and integrating the
    e^{τ−h}-weighted polynomial EXACTLY gives
    ``x_{n+1} = e^{−h} x_n + I0·D_n + (h − I0)·(D_n − D_{n−1})/h_prev``
    (I0 = 1−e^{−h}) — this differs from dpmpp_2m, whose correction uses
    the midpoint coefficient 1/(2r) instead of the exact first-moment
    integral. ``eta > 0`` adds an ancestral split per step (the
    ``res_2m_ancestral`` entry binds eta=1)."""

    def step(carry, i):
        x, old_denoised, h_prev, have_old = carry
        with device_scope("sampler"):
            sigma, sigma_next = sigmas[i], sigmas[i + 1]
        denoised = denoise(x, sigma)
        with device_scope("sampler"):
            if eta:
                sigma_down, sigma_up = _ancestral_sigmas(sigma, sigma_next, eta)
            else:
                sigma_down, sigma_up = sigma_next, jnp.zeros(())
            h = _t_of(sigma_down) - _t_of(sigma)
            i0 = _i0(h)
            slope = (denoised - old_denoised) / jnp.maximum(h_prev, 1e-10)
            x_new = jnp.exp(-h) * x + i0 * denoised \
                + jnp.where(have_old, (h - i0), 0.0) * slope
            if eta:
                noise = jax.random.normal(jax.random.fold_in(key, i),
                                          x.shape, x.dtype)
                x_new = x_new + noise * sigma_up
            x_new = jnp.where(sigma_next > 0, x_new, denoised)
            h_real = _t_of(sigma_next) - _t_of(sigma)
            return (x_new, denoised, h_real, jnp.array(True))

    return SamplerProgram(
        "res_2m", sigmas.shape[0] - 1,
        lambda x: (x, jnp.zeros_like(x), jnp.zeros(()), jnp.array(False)),
        step, _extract_first)


def _res_2s_program(denoise, sigmas, key=None, eta: float = 0.0,
                    c2: float = 0.5) -> SamplerProgram:
    """RES second-order single-step (``res_2s``): two-stage exponential
    Runge–Kutta (Hochbruck–Ostermann ExpRK2) with midpoint stage c2.

    Stage:  ``x_s = e^{−c2·h} x + I0(c2·h)·D_n`` at σ_s = σ·e^{−c2·h};
    update: ``x_{n+1} = e^{−h} x + (I0 − Ψ)·D_n + Ψ·D_s`` with
    ``Ψ = (h − I0)/(c2·h)`` — satisfying the order-2 conditions
    b1+b2 = φ1, b2·c2 = φ2 for any c2 ∈ (0, 1]. Two model calls per
    step. ``eta > 0`` adds an ancestral split (``res_2s_ancestral``)."""

    def step(carry, i):
        (x,) = carry
        with device_scope("sampler"):
            sigma, sigma_next = sigmas[i], sigmas[i + 1]
        denoised = denoise(x, sigma)
        with device_scope("sampler"):
            if eta:
                sigma_down, sigma_up = _ancestral_sigmas(sigma, sigma_next,
                                                         eta)
            else:
                sigma_down, sigma_up = sigma_next, jnp.zeros(())

        def last(_):
            return denoised

        def stage(_):
            with device_scope("sampler"):
                h = _t_of(sigma_down) - _t_of(sigma)
                ch = c2 * h
                x_s = jnp.exp(-ch) * x + _i0(ch) * denoised
                sigma_s = sigma * jnp.exp(-ch)
            denoised_s = denoise(x_s, sigma_s)
            with device_scope("sampler"):
                i0 = _i0(h)
                psi = (h - i0) / jnp.maximum(ch, 1e-10)
                return jnp.exp(-h) * x + (i0 - psi) * denoised \
                    + psi * denoised_s

        x_new = jax.lax.cond(sigma_next > 0, stage, last, None)
        if eta:
            with device_scope("sampler"):
                noise = jax.random.normal(jax.random.fold_in(key, i),
                                          x.shape, x.dtype)
                x_new = x_new + jnp.where(sigma_next > 0, noise * sigma_up,
                                          0.0)
        return (x_new,)

    return SamplerProgram("res_2s", sigmas.shape[0] - 1,
                          lambda x: (x,), step, _extract_first)


def _dpmpp_3m_sde_program(denoise, sigmas, key, eta: float = 1.0,
                          s_noise: float = 1.0) -> SamplerProgram:
    """DPM-Solver++(3M) SDE: third-order multistep with exponential-decay
    noise (the k-diffusion ``sample_dpmpp_3m_sde`` algorithm, transcribed
    from its published update rule into a scan).

    Per step (h = Δt, h_eta = h·(eta+1)):
    ``x' = e^{−h_eta} x + I0(h_eta)·D`` plus, once two/three history
    points exist, divided-difference corrections weighted by
    ``φ2 = I0/h_eta·(−1)+1 … φ3 = φ2/h_eta − ½`` exactly as published;
    noise scale ``σ_next·√(1 − e^{−2·h·eta})``."""

    def step(carry, i):
        x, d1, d2, h1, h2, count = carry
        with device_scope("sampler"):
            sigma, sigma_next = sigmas[i], sigmas[i + 1]
        denoised = denoise(x, sigma)
        with device_scope("sampler"):

            def last(_):
                return denoised, jnp.zeros(())

            def stage(_):
                h = _t_of(sigma_next) - _t_of(sigma)
                h_eta = h * (eta + 1.0)
                x_new = jnp.exp(-h_eta) * x + _i0(h_eta) * denoised
                phi2 = jnp.expm1(-h_eta) / h_eta + 1.0
                phi3 = phi2 / h_eta - 0.5
                r0 = h1 / h
                r1 = h2 / h
                d1_0 = (denoised - d1) / jnp.maximum(r0, 1e-10)
                d1_1 = (d1 - d2) / jnp.maximum(r1, 1e-10)
                dd1 = d1_0 + (d1_0 - d1_1) * r0 / jnp.maximum(r0 + r1, 1e-10)
                dd2 = (d1_0 - d1_1) / jnp.maximum(r0 + r1, 1e-10)
                third = x_new + phi2 * dd1 - phi3 * dd2
                second = x_new + phi2 * d1_0
                x_new = jnp.where(count >= 2, third,
                                  jnp.where(count == 1, second, x_new))
                if eta:
                    noise = jax.random.normal(jax.random.fold_in(key, i),
                                              x.shape, x.dtype)
                    x_new = x_new + noise * sigma_next * s_noise * jnp.sqrt(
                        jnp.maximum(-jnp.expm1(-2.0 * h * eta), 0.0))
                return x_new, h

            x_new, h = jax.lax.cond(sigma_next > 0, stage, last, None)
            return (x_new, denoised, d1, h, h1, count + 1)

    return SamplerProgram(
        "dpmpp_3m_sde", sigmas.shape[0] - 1,
        lambda x: (x, jnp.zeros_like(x), jnp.zeros_like(x), jnp.zeros(()),
                   jnp.zeros(()), jnp.int32(0)),
        step, _extract_first)


def _uni_pc_program(denoise, sigmas, key=None) -> SamplerProgram:
    """UniPC (UniP-2 predictor + UniC-3 corrector), data-prediction form,
    one model call per step (the corrector reuses the evaluation made at
    the predicted point, per the published predictor–corrector scheme).

    Both pieces integrate ∫ e^{τ−h} P(τ) dτ exactly for a polynomial P
    through the available D points (moments I0 = 1−e^{−h}, I1 = h−I0,
    I2 = h²−2·I1):

    - predictor: linear P through (−h_prev, D_{n−1}), (0, D_n) — the
      same exponential-Adams update as ``res_2m``;
    - corrector (applied to the PREVIOUS transition once D at the
      predicted point is known): quadratic P through (−h_prev, D_{n−1}),
      (0, D_n), (h, D̂_{n+1}), third-order accurate; falls back to the
      exponential-trapezoidal (linear through 0, h) on the first
      transition."""
    del key

    def correct(x_prev, d_prev2, d_prev, d_cur, h, h_prev, count):
        """Re-integrate t_{n−1}→t_n with D̂ at the arrival point."""
        i0 = _i0(h)
        i1 = h - i0
        i2 = h * h - 2.0 * i1
        # trapezoidal (first transition): linear through (0,d_prev),(h,d_cur)
        b_lin = (d_cur - d_prev) / jnp.maximum(h, 1e-10)
        trap = jnp.exp(-h) * x_prev + i0 * d_prev + i1 * b_lin
        # quadratic through (−h_prev, d_prev2), (0, d_prev), (h, d_cur)
        hp = jnp.maximum(h_prev, 1e-10)
        hh = jnp.maximum(h, 1e-10)
        # solve P(τ)=d_prev + bτ + cτ²:  b·h + c·h² = d_cur − d_prev
        #                               −b·hp + c·hp² = d_prev2 − d_prev
        det = hh * hp * (hh + hp)
        b = (hp * hp * (d_cur - d_prev) - hh * hh * (d_prev2 - d_prev)) / det
        c = (hp * (d_cur - d_prev) + hh * (d_prev2 - d_prev)) / det
        quad = jnp.exp(-h) * x_prev + i0 * d_prev + i1 * b + i2 * c
        return jnp.where(count >= 2, quad, trap)

    def predict(x_cur, d_cur, d_prev, h, h_prev, count):
        i0 = _i0(h)
        slope = (d_cur - d_prev) / jnp.maximum(h_prev, 1e-10)
        return jnp.exp(-h) * x_cur + i0 * d_cur \
            + jnp.where(count >= 1, h - i0, 0.0) * slope

    def step(carry, i):
        # x_pred: predicted state at σ_i (uncorrected); x_prev: corrected
        # state at σ_{i−1}; d_prev/d_prev2: D at σ_{i−1}/σ_{i−2}
        x_prev, x_pred, d_prev, d_prev2, h_prev, h_prev2, count = carry
        with device_scope("sampler"):
            sigma, sigma_next = sigmas[i], sigmas[i + 1]
        d_cur = denoise(x_pred, sigma)
        with device_scope("sampler"):
            # corrector for the transition that produced x_pred
            x_cur = jnp.where(
                count >= 1,
                correct(x_prev, d_prev2, d_prev, d_cur, h_prev, h_prev2, count),
                x_pred)
            h = _t_of(sigma_next) - _t_of(sigma)
            x_next = predict(x_cur, d_cur, d_prev, h, h_prev, count)
            x_next = jnp.where(sigma_next > 0, x_next, d_cur)
            return (x_cur, x_next, d_cur, d_prev, h, h_prev, count + 1)

    return SamplerProgram(
        "uni_pc", sigmas.shape[0] - 1,
        lambda x: (x, x, jnp.zeros_like(x), jnp.zeros_like(x), jnp.zeros(()),
                   jnp.zeros(()), jnp.int32(0)),
        step, lambda carry: carry[1])


PROGRAMS: dict[str, Callable] = {
    "euler": _euler_program,
    "euler_ancestral": _euler_ancestral_program,
    "heun": _heun_program,
    "dpmpp_2m": _dpmpp_2m_program,
    "ddim": _ddim_program,
    "lcm": _lcm_program,
    "dpmpp_sde": _dpmpp_sde_program,
    "dpmpp_2m_sde": _dpmpp_2m_sde_program,
    "res_2m": _res_2m_program,
    "res_2s": _res_2s_program,
    "res_2m_ancestral": lambda d, s, key=None, **kw: _res_2m_program(
        d, s, key, eta=kw.pop("eta", 1.0), **kw),
    "res_2s_ancestral": lambda d, s, key=None, **kw: _res_2s_program(
        d, s, key, eta=kw.pop("eta", 1.0), **kw),
    "dpmpp_3m_sde": _dpmpp_3m_sde_program,
    "uni_pc": _uni_pc_program,
}


def make_program(name: str, denoise: Denoiser, sigmas: jax.Array,
                 key: Optional[jax.Array] = None,
                 **kwargs) -> SamplerProgram:
    """The resumable form of :func:`sample`: same dispatch, same kwargs,
    but the ``(init, step, extract)`` triple instead of a finished run —
    segment it with :func:`run_segment` (diffusion/checkpoint.py)."""
    try:
        builder = PROGRAMS[name]
    except KeyError:
        raise ValueError(f"unknown sampler {name!r}; have {sorted(PROGRAMS)}")
    return builder(denoise, sigmas, key, **kwargs)


def carry_structure(name: str, x_struct, **kwargs) -> tuple:
    """Abstract carry shapes for sampler ``name`` given the latent's
    ``ShapeDtypeStruct`` — no denoiser needed (``init`` is pure
    structure). The preemptible pipeline derives shard_map specs and the
    checkpoint layout from this."""
    prog = make_program(name, None, jnp.zeros((2,), jnp.float32),
                        key=None, **kwargs)
    return jax.eval_shape(prog.init, x_struct)


def extract_output(name: str, carry: tuple, **kwargs) -> jax.Array:
    """Pick sampler ``name``'s output slot out of a finished carry —
    denoiser-free (used by the preemptible pipeline's decode program)."""
    prog = make_program(name, None, jnp.zeros((2,), jnp.float32),
                        key=None, **kwargs)
    return prog.extract(carry)


# --- a decode loop is a sampler too ------------------------------------------


def token_program(forward, n_steps: int, key: jax.Array, temperature,
                  tap_every: int, n_counts: int) -> SamplerProgram:
    """Autoregressive decoding as a :class:`SamplerProgram`, run by the
    same :func:`run_segment` / :func:`run_program` drivers as the
    denoisers: one step draws a token from the carried logits and runs
    the model on it, ``forward(state, token, i) -> (logits [V] f32,
    state, counts [n_counts] int32)``.

    ``init((logits, state))`` takes what prefill left: the last prompt
    position's logits and the model's state (any pytree: recurrent
    states, convolution tails, a latent cache). The carry is ``(ids
    [n_steps], logits, state, taps, counts, finite)``: the drawn ids
    (``extract``), the logits the next draw will use, every
    ``tap_every``-th step's logits as computed (``taps``
    [n_steps // tap_every, V]: what a parity check compares), the sum of
    ``forward``'s counts, and whether every logit drawn from was finite.
    Step ``i`` draws with ``fold_in(key, i)`` of the GLOBAL index, so a
    run cut into segments is the run uncut. ``temperature`` may be
    traced; 0 is greedy. The carry is not the latent-shaped carry of the
    denoisers' contract above: it is for one device, never sharded."""
    tap_every = max(1, int(tap_every))

    def init(x):
        logits, state = x
        return (jnp.zeros((n_steps,), jnp.int32), logits, state,
                jnp.zeros((n_steps // tap_every, logits.shape[-1]),
                          jnp.float32),
                jnp.zeros((n_counts,), jnp.int32), jnp.asarray(True))

    def step(carry, i):
        ids, logits, state, taps, counts, finite = carry
        with device_scope("llm_sample"):
            finite = finite & jnp.isfinite(logits).all()
            gumbel = jax.random.gumbel(jax.random.fold_in(key, i),
                                       logits.shape, jnp.float32)
            token = jnp.argmax(logits + temperature * gumbel).astype(
                jnp.int32)
            ids = jax.lax.dynamic_update_index_in_dim(ids, token, i, 0)
        logits, state, seen = forward(state, token, i)
        with device_scope("llm_sample"):
            if taps.shape[0]:
                slot = jnp.minimum(i // tap_every, taps.shape[0] - 1)
                row = jnp.where((i + 1) % tap_every == 0, logits,
                                jax.lax.dynamic_index_in_dim(taps, slot, 0,
                                                             False))
                taps = jax.lax.dynamic_update_index_in_dim(taps, row, slot,
                                                           0)
            return (ids, logits, state, taps, counts + seen, finite)

    return SamplerProgram("token", n_steps, init, step, _extract_first)


# --- the classic one-shot API (unchanged signatures) ------------------------


def sample_euler(denoise: Denoiser, x: jax.Array, sigmas: jax.Array,
                 key: jax.Array | None = None) -> jax.Array:
    return run_program(_euler_program(denoise, sigmas, key), x)


def sample_euler_ancestral(denoise: Denoiser, x: jax.Array, sigmas: jax.Array,
                           key: jax.Array, eta: float = 1.0) -> jax.Array:
    return run_program(_euler_ancestral_program(denoise, sigmas, key,
                                                eta=eta), x)


def sample_heun(denoise: Denoiser, x: jax.Array, sigmas: jax.Array,
                key: jax.Array | None = None) -> jax.Array:
    return run_program(_heun_program(denoise, sigmas, key), x)


def sample_dpmpp_2m(denoise: Denoiser, x: jax.Array, sigmas: jax.Array,
                    key: jax.Array | None = None) -> jax.Array:
    return run_program(_dpmpp_2m_program(denoise, sigmas, key), x)


def sample_ddim(denoise: Denoiser, x: jax.Array, sigmas: jax.Array,
                key: jax.Array | None = None, eta: float = 0.0) -> jax.Array:
    return run_program(_ddim_program(denoise, sigmas, key, eta=eta), x)


def sample_lcm(denoise: Denoiser, x: jax.Array, sigmas: jax.Array,
               key: jax.Array) -> jax.Array:
    return run_program(_lcm_program(denoise, sigmas, key), x)


def sample_dpmpp_sde(denoise: Denoiser, x: jax.Array, sigmas: jax.Array,
                     key: jax.Array, eta: float = 1.0, s_noise: float = 1.0,
                     r: float = 0.5) -> jax.Array:
    return run_program(_dpmpp_sde_program(denoise, sigmas, key, eta=eta,
                                          s_noise=s_noise, r=r), x)


def sample_dpmpp_2m_sde(denoise: Denoiser, x: jax.Array, sigmas: jax.Array,
                        key: jax.Array, eta: float = 1.0,
                        s_noise: float = 1.0) -> jax.Array:
    return run_program(_dpmpp_2m_sde_program(denoise, sigmas, key, eta=eta,
                                             s_noise=s_noise), x)


def sample_res_2m(denoise: Denoiser, x: jax.Array, sigmas: jax.Array,
                  key: jax.Array | None = None, eta: float = 0.0) -> jax.Array:
    return run_program(_res_2m_program(denoise, sigmas, key, eta=eta), x)


def sample_res_2s(denoise: Denoiser, x: jax.Array, sigmas: jax.Array,
                  key: jax.Array | None = None, eta: float = 0.0,
                  c2: float = 0.5) -> jax.Array:
    return run_program(_res_2s_program(denoise, sigmas, key, eta=eta,
                                       c2=c2), x)


def sample_dpmpp_3m_sde(denoise: Denoiser, x: jax.Array, sigmas: jax.Array,
                        key: jax.Array, eta: float = 1.0,
                        s_noise: float = 1.0) -> jax.Array:
    return run_program(_dpmpp_3m_sde_program(denoise, sigmas, key, eta=eta,
                                             s_noise=s_noise), x)


def sample_uni_pc(denoise: Denoiser, x: jax.Array, sigmas: jax.Array,
                  key: jax.Array | None = None) -> jax.Array:
    return run_program(_uni_pc_program(denoise, sigmas, key), x)


SAMPLERS: dict[str, Callable] = {
    "euler": sample_euler,
    "euler_ancestral": sample_euler_ancestral,
    "heun": sample_heun,
    "dpmpp_2m": sample_dpmpp_2m,
    "ddim": sample_ddim,
    "lcm": sample_lcm,
    "dpmpp_sde": sample_dpmpp_sde,
    "dpmpp_2m_sde": sample_dpmpp_2m_sde,
    "res_2m": sample_res_2m,
    "res_2s": sample_res_2s,
    "res_2m_ancestral": lambda d, x, s, key=None, **kw: sample_res_2m(
        d, x, s, key, eta=kw.pop("eta", 1.0), **kw),
    "res_2s_ancestral": lambda d, x, s, key=None, **kw: sample_res_2s(
        d, x, s, key, eta=kw.pop("eta", 1.0), **kw),
    "dpmpp_3m_sde": sample_dpmpp_3m_sde,
    "uni_pc": sample_uni_pc,
}


def sample(
    name: str,
    denoise: Denoiser,
    x: jax.Array,
    sigmas: jax.Array,
    key: jax.Array | None = None,
    **kwargs,
) -> jax.Array:
    try:
        fn = SAMPLERS[name]
    except KeyError:
        raise ValueError(f"unknown sampler {name!r}; have {sorted(SAMPLERS)}")
    return fn(denoise, x, sigmas, key, **kwargs)
