"""Compressed data-parallel training through the program's own training loop.

The run is built and stepped by the two functions the training launcher
itself uses, ``repro.launch.train.build`` and ``repro.launch.train.step_loop``,
from the launcher's own arguments (``--mesh data=<chips> --compress-grads
<policy> --no-smoke``, the traffic's batch and sequence length, ``--seed``).
Nothing of the plan, the shardings or the step is rebuilt here.  The model
widths the configuration file states are checked against the program's
configuration before the first step.

The cell's tokens are its own (:class:`UniformTokens`): ids drawn uniformly
from the whole vocabulary, from ``--seed`` and the step index, fed to the
loop through the pipeline's ``batch_at(k)`` interface in place of the
program's synthetic stream.

Set-up builds the run and takes ``WARMUP_STEPS`` steps (compilation
included).  The window then runs whole steps, each on the next batch of the
token stream, until the first step that ends after ``--seconds``.

``compress_gbps``: the bf16 bytes of one gradient vector, which enter the
compressed reduction once a step, times the window's steps, over the summed
wall time of those steps, each timed by the loop from its batch to its loss
on the host.  ``attempted`` counts steps and ``failed`` non-finite losses.

Correct, from what the timed path produced: for one step ``k`` drawn from
the seed among the window's first 20, the parameters, Adam's first moment
``m_k`` and the error-feedback residual ``r_k`` are read to the host before
the call, and ``m_{k+1}``, ``r_{k+1}``, ``grad_norm`` and the loss after it,
outside the step's timing.  After the window the program's state is freed
and the gradient the optimizer applied is recovered from the moment update,
``a = (m_{k+1} - b1 m_k) / ((1 - b1) clip)``, ``clip`` from ``grad_norm``.
Three checks decide ``correct`` (see ``LIMITS``), the first two against
this benchmark's own reference (``lib/lm_reference.py``):

* ``grad_err_over_bound``: the largest ``|r_{k+1}|`` over the reference's
  int8 block bound of the codec's input ``a + r_{k+1}``.  ``a`` is the
  decoded gradient rounded to bf16 (the parameters' dtype) and recovered
  through float32 arithmetic, so the bound is taken over every input within
  a stated slack of ``a + r_{k+1}``: half a bf16 ulp of each element
  (``2**-8 |a|``), the float32 rounding of the recovery, and the float32
  rounding of ``r_{k+1}`` itself.
* ``grad_rel_err``: ``||(a + r_{k+1} - r_k) - g_ref|| / ||g_ref||``, with
  ``g_ref`` the reference's float32 mean of the four chips' gradients at the
  step's parameters on the step's batch, one sequence at a time.
* ``replica_mismatch``: the chips whose copy of ``m_{k+1}`` differs, bit for
  bit, from the first chip's (the moments are replicated; the other checks
  read the first chip's copy).

``loss_rel_err``, the step's loss against the reference's, is printed on
standard error beside them but decides nothing: on a v5e x4 the reference
with its matrix operands in float8 e4m3, put in the program's place, read
2.3e-6 at step 21, below the program's own 3.9e-6 to 6.4e-6 over three
seeds, so no limit lies between the two.
"""
from __future__ import annotations

import functools
import gc
import math
import sys
import time

import numpy as np

from lib import lm_reference
from lib.harness import Check, RunResult

#: the limit of each check, with its reason
LIMITS = {
    # the codec's own guarantee: no element of the residual is larger than
    # the bound the policy may claim for its block
    "grad_err_over_bound": 1.0,
    # the program computes and reduces in bf16, the reference in float32.
    # On a v5e x4 the program read 0.0234, 0.0209 and 0.0143 at checked
    # steps 2, 12 and 21 (three seeds), and the reference with every matrix
    # operand rounded to float8 e4m3, the next precision below bf16, put in
    # the program's place, 0.676 at step 21: 3.4 times room above the
    # program and 8.5 times below the float8 reading
    "grad_rel_err": 0.08,
    # every chip applies the same decoded gradient to the same state, so
    # the replicated moments are one value, bit for bit
    "replica_mismatch": 0.0,
}

#: steps taken in set-up: the first call compiles the step, the second
#: compiles it again for the donated state in its resharded layout
WARMUP_STEPS = 2

#: the window's first steps, among which the checked step is drawn
CHECK_AMONG = 20
#: elements of the flat vectors compared at a time on the device
CHECK_CHUNK = 1 << 25
_MODEL_KEYS = ("family", "n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
               "vocab", "qkv_bias", "tie_embeddings", "mlp_act", "dtype", "rope_theta")


class UniformTokens:
    """The cell's token stream: every id uniform over ``[0, vocab)``, drawn
    from the seed and the step index, so ``batch_at(k)`` is a pure function
    of both, as the program's pipelines are.  The label of a position is
    the next token; the last position has none (``-1``)."""

    def __init__(self, vocab: int, seq: int, batch: int, seed: int):
        self.vocab, self.seq, self.batch, self.seed = vocab, seq, batch, seed

    def batch_at(self, k: int):
        rng = np.random.default_rng([self.seed, 1, k])
        tokens = rng.integers(0, self.vocab, (self.batch, self.seq), dtype=np.int32)
        labels = np.roll(tokens, -1, axis=1)
        labels[:, -1] = -1
        return {"tokens": tokens, "labels": labels}


def launcher_argv(config, traffic, seed: int, chips: int):
    model = config["model"]
    return ["--arch", model["arch"],
            "--no-smoke" if model["preset"] == "published" else "--smoke",
            "--mesh", f"data={chips}",
            "--compress-grads", traffic["grad_policy"],
            "--seq", str(traffic["seq"]),
            "--batch", str(traffic["global_batch"]),
            "--steps", str(config["assumed"]["schedule_horizon_steps"]),
            "--seed", str(seed)]


def check_run(run, config, traffic, chips: int) -> None:
    """The program's model, optimizer and plan are what the file states."""
    model, cfg = config["model"], run.cfg
    stated = {k: model[k] for k in _MODEL_KEYS}
    have = {k: getattr(cfg, k) for k in _MODEL_KEYS}
    have["head_dim"] = cfg.hd
    stated["head_dim"] = model["head_dim"]
    have["embedding_rows"] = cfg.padded_vocab
    stated["embedding_rows"] = config["assumed"]["embedding_rows"]
    if stated != have:
        raise ValueError(f"configuration file {stated} != program {have}")
    opt = config["assumed"]["optimizer"]
    for k in ("lr", "b1", "b2", "eps", "weight_decay", "grad_clip"):
        if not math.isclose(float(getattr(run.opt, k)), float(opt[k])):
            raise ValueError(f"optimizer {k}: program {getattr(run.opt, k)} != file {opt[k]}")
    if run.opt.compress_moments:
        raise ValueError("the cell states float32 moments")
    pol = run.plan.grad_compression()
    if run.plan.dp != chips or pol is None or pol.bs != traffic["block_size"]:
        raise ValueError(f"plan dp={run.plan.dp} policy={pol} is not the cell's")


def _host(tree):
    import jax

    return jax.device_get(tree)


def replica_mismatch(tree) -> int:
    """Copies of a shard that differ, bit for bit, from the first copy of
    the same shard, over the leaves of ``tree``.  Read one leaf at a time."""
    import jax

    bad = 0
    for leaf in jax.tree.leaves(tree):
        first = {}
        for s in leaf.addressable_shards:
            key = tuple((i.start, i.stop, i.step) for i in s.index)
            x = np.asarray(s.data).view(np.uint8)
            if key in first:
                bad += not np.array_equal(first[key], x)
            else:
                first[key] = x
    return bad


def _flat(tree) -> np.ndarray:
    import jax

    return np.concatenate([np.asarray(x, np.float32).reshape(-1)
                           for x in jax.tree.leaves(tree)])


def run(h) -> RunResult:
    import jax

    from repro.launch import train

    config, traffic = h.cell.config, h.cell.traffic
    tr = train.build(launcher_argv(config, traffic, h.seed, h.cell.chips))
    check_run(tr, config, traffic, h.cell.chips)
    tr.pipe = UniformTokens(tr.cfg.vocab, traffic["seq"], traffic["global_batch"], h.seed)
    warm = WARMUP_STEPS
    train.step_loop(tr, range(warm))

    k_check = warm + int(np.random.default_rng([h.seed, 2]).integers(CHECK_AMONG))
    seen = {}

    def before(k, state):
        if k == k_check:
            seen["before"] = _host({"params": state["params"],
                                    "m": state["opt"]["m"], "r": state["feedback"]})

    def after(k, state, metrics, dt):
        if k == k_check:
            seen["after"] = _host({"m": state["opt"]["m"], "r": state["feedback"],
                                   "grad_norm": metrics["grad_norm"],
                                   "loss": metrics["loss"]})
            seen["replica_mismatch"] = replica_mismatch(state["opt"]["m"])

    h.start_profile()
    deadline = h.begin_window()

    def steps():
        k = warm
        while k <= k_check or time.perf_counter() < deadline:
            yield k
            k += 1

    losses, seconds = train.step_loop(tr, steps(), before=before, after=after)
    h.end_window()

    n_params = sum(x.size for x in jax.tree.leaves(seen["before"]["params"]))
    grad_bytes = 2 * n_params  # one bf16 gradient vector a step
    cfg, opt, plan = tr.cfg, tr.opt, tr.plan
    tokens_per_step = tr.tokens_per_step
    batch = tr.pipe.batch_at(k_check)  # the checked step's batch, as it was fed
    del tr  # free the program's state before the reference runs
    gc.collect()
    readings = grad_checks(seen["before"], seen["after"], batch, cfg,
                           b1=opt.b1, grad_clip=opt.grad_clip, dp=plan.dp,
                           radius=_radius(traffic), bs=int(traffic["block_size"]),
                           device=h.devices[0])
    readings["replica_mismatch"] = seen["replica_mismatch"]
    n = len(seconds)
    print(f"train_dp: {n} steps, median {np.median(seconds):.4f} s, "
          f"{n * tokens_per_step / sum(seconds):,.0f} tok/s; checked step {k_check}: "
          f"{readings}", file=sys.stderr, flush=True)
    checks = [Check(name, float(readings[name]), limit) for name, limit in LIMITS.items()]
    e2e = {"compress_gbps": n * grad_bytes / 1e9 / sum(seconds)}
    work = {"traced_steps": n, "tokens_per_step": tokens_per_step}
    return RunResult(e2e=e2e, checks=checks, attempted=n,
                     failed=sum(not math.isfinite(v) for v in losses), work=work)


def _radius(traffic) -> int:
    """The fixed tier's radius of the cell's policy (``core/jitmode.py``)."""
    return {"int8": 127, "int4": 7}[traffic["grad_policy"].split(":")[0]]


def grad_checks(before, after, batch, cfg, *, b1: float, grad_clip: float, dp: int,
                radius: int, bs: int, device=None):
    """The three readings of the module docstring, from the host copies of
    one step's inputs (``before``: params, m, r) and outputs (``after``: m,
    r, grad_norm, loss)."""
    import jax
    import jax.numpy as jnp

    with jax.default_device(device):
        ref_loss, g_ref = lm_reference.loss_and_grad(
            before["params"], batch["tokens"], batch["labels"], cfg, dp=dp)
        g_ref = _flat(_host(g_ref))
    m0, m1 = _flat(before["m"]), _flat(after["m"])
    r0 = np.asarray(before["r"], np.float32)
    r1 = np.asarray(after["r"], np.float32)
    n = m0.size
    gnorm = np.float32(after["grad_norm"])
    clip = clip_factor(gnorm, grad_clip)
    step = _check_chunk_fn(radius, bs)
    worst, diff_sq, ref_sq = 0.0, 0.0, 0.0
    total = r1.size  # a whole number of blocks, as is the chunk
    chunk = min(CHECK_CHUNK, total)
    for lo in range(0, total, chunk):
        hi = min(lo + chunk, total)
        parts = [_pad(x[lo:min(hi, n)] if lo < n else x[:0], hi - lo)
                 for x in (m0, m1, g_ref)]
        parts += [r0[lo:hi], r1[lo:hi]]
        parts = [_pad(p, chunk) for p in parts]
        valid = np.arange(lo, lo + chunk) < n
        with jax.default_device(device):
            w, d2, g2 = step(*(jnp.asarray(p) for p in parts), jnp.asarray(valid),
                             np.float32(b1), clip)
        worst, diff_sq, ref_sq = max(worst, float(w)), diff_sq + float(d2), ref_sq + float(g2)
    loss = float(after["loss"])
    return {"grad_err_over_bound": worst,
            "grad_rel_err": math.sqrt(diff_sq / ref_sq) if ref_sq > 0 else math.inf,
            "loss_rel_err": abs(loss - ref_loss) / abs(ref_loss),
            "loss": loss, "ref_loss": ref_loss, "grad_norm": float(gnorm)}


def clip_factor(grad_norm, grad_clip: float) -> np.float32:
    """The optimizer's global-norm clip factor, in float32."""
    gnorm = np.float32(grad_norm)
    return np.minimum(np.float32(1.0),
                      np.float32(grad_clip) / np.maximum(gnorm, np.float32(1e-12)))


def _pad(x: np.ndarray, size: int) -> np.ndarray:
    return np.pad(x.astype(np.float32), (0, size - x.size))


@functools.lru_cache(maxsize=None)
def _check_chunk_fn(radius: int, bs: int):
    """One jitted program for a chunk of the flat vectors: the largest
    residual over its bound, and the two sums of squares of grad_rel_err.
    ``valid`` marks the elements of the gradient (the rest is the feedback's
    padding, where the codec's input is ``r_k`` itself)."""
    import jax
    import jax.numpy as jnp

    def fn(m0, m1, g_ref, r0, r1, valid, b1, clip):
        denom = (1.0 - b1) * clip
        a = (m1 - b1 * m0) / denom
        inv = (jnp.abs(m1) + b1 * jnp.abs(m0)) / denom
        slack = jnp.where(
            valid,
            jnp.float32(2.0 ** -8) * jnp.abs(a)  # a is the decode rounded to bf16
            + jnp.float32(2.0 ** -20) * (inv + jnp.abs(a))  # the recovery's float32
            + jnp.float32(2.0 ** -126),
            0.0) + jnp.float32(2.0 ** -24) * jnp.abs(r1)  # r_{k+1}'s own rounding
        x = jnp.where(valid, a + r1, r0)  # the codec's input, within the slack
        bound = lm_reference.block_bound(x, slack, radius=radius, bs=bs)
        worst = jnp.max(jnp.max(jnp.abs(r1).reshape(-1, bs), axis=-1) / bound)
        g = jnp.where(valid, a + r1 - r0, 0.0)
        d = g - g_ref
        return worst, jnp.sum(d * d), jnp.sum(g_ref * g_ref)

    return jax.jit(fn)
