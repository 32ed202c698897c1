"""Training cells: the program's compiled train step, driven from the seed.

Set-up builds one object, the jitted step (state donated) with its state,
and drives it through its first two steps on the batches that the window's
own generator makes for them; step 1 compiles, and step 2 is the first
call that takes the step's own output as its input.  It keeps what the
optimizer got from step 1: the loss, the decoded gradient (AdamW's first
moment m = (1 - b1) g, copied to the host) and each leaf's change of
parameters.  The window then continues the same state from step 3 until
the first step boundary after ``--seconds``.  After the window the state is freed
and the plain reference (``bench/reference.py``) computes step 1 from the
same seed, one row of the batch at a time.

The codec adds N(0, sigma^2) to every coordinate of the gradient, which
the reference cannot draw without the program's tables.  So the decoded
gradient is compared by regression: least squares of its per-leaf
centred values on the reference's per-leaf centred gradients of each row
gives one weight per row, each 1 / rows where the backward is the mean
over all rows.  Centring per leaf takes out the per-tensor offset of the
shared (A, B) draw; what is left of the noise moves a weight by about
sigma over the row gradient's norm.

Compared, each with its limit (PERF.md gives the readings each limit was
set from):

* ``row_weight_gap``: max over rows of |rows x weight - 1|;
* ``update_norm_gap``: each leaf's change of parameters at step 1
  against the reference's AdamW step on its own gradient plus its own
  N(0, sigma^2) draw, as |norm - ref norm| over the larger of the leaf's
  ref norm and the median leaf's, worst leaf.

Step 1's loss is logged beside the reference's and not compared: on the
chip neither the float8 control nor any fault separates its gap from the
sound program's.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import common
import reference
import synthetic


def _leaf_paths(tree):
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _diff_norms(a, b):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                      for x in jax.tree.leaves(tree)])


def _centred(x):
    x = x.astype(jnp.float32)
    return x - jnp.mean(x)


@jax.jit
def _centre_bf16(tree):
    return jax.tree.map(lambda x: _centred(x).astype(jnp.bfloat16), tree)


@jax.jit
def _dot(a, b):
    return sum(jnp.vdot(_centred(x), _centred(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def norm_gap(prog, ref):
    """Worst leaf's |prog - ref| over max(ref, median ref)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    gaps = np.abs(prog - ref) / np.maximum(ref, float(np.median(ref)))
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.t = ctx.traffic
        self.sizes = common.dense_sizes(ctx.config)
        self.rows = int(self.t["batch"])
        self.seq = int(self.t["seq_len"])
        self.tokens_per_step = self.rows * self.seq
        self.opt = self.t["optimizer"]
        self.sigma = float(self.t["codec"]["sigma"])
        self.clip = float(self.t["codec"]["clip"])
        self.key = common.seed_key(ctx.seed)
        self.codec_seed = jnp.int32(ctx.seed & 0x7FFFFFFF)
        self.prog = {}

    # ------------------------------------------------------------- set-up
    def setup(self):
        from repro.dist import meshctx
        from repro.dist.compress import CompressionConfig
        from repro.train import steps

        ctx, t = self.ctx, self.t
        cfg = common.program_config(ctx.config)
        if ctx.chips != 1:
            raise ValueError("training cells run one client on one chip")
        mesh = meshctx.make_mesh((1, 1), ("data", "model"),
                                 devices=jax.devices()[:1])
        meshctx.set_mesh(mesh)
        c = t["codec"]
        comp = CompressionConfig(
            mechanism=c["mechanism"], sigma=c["sigma"], clip=c["clip"],
            per_coord=c["per_coord"], fused=c["fused"],
            msg_bits=c["msg_bits"])
        o = self.opt
        if (o["name"], o["b1"], o["b2"], o["eps"], o["weight_decay"]) != (
                "adamw", 0.9, 0.95, 1e-8, 0.0):
            raise ValueError("the program's AdamW has b1 0.9, b2 0.95, "
                             "eps 1e-8 and no weight decay")
        tc = steps.TrainConfig(optimizer="adamw", lr=o["lr"],
                               compression=comp)
        abstract = steps.make_train_state_specs(cfg, tc)
        shardings = steps.train_state_shardings(cfg, tc, mesh)

        def make_state(key):
            params = reference.init_params(key, abstract["params"])
            zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                                 {"opt_state": abstract["opt_state"],
                                  "step": abstract["step"]})
            return {"params": params, **zeros}

        self.make_params = jax.jit(
            lambda key: reference.init_params(key, abstract["params"]),
            out_shardings=shardings["params"])
        state = jax.jit(make_state, out_shardings=shardings)(self.key)
        replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        self.batch = jax.jit(
            lambda k: {"tokens": synthetic.lm_batch(
                k, self.rows, self.seq, self.sizes["vocab"])},
            out_shardings={"tokens": replicated})
        self.step = jax.jit(steps.build_train_step(cfg, tc, mesh),
                            donate_argnums=0)
        self.paths = _leaf_paths(state["params"])

        state, m = self.step(state, self.batch(synthetic.step_key(self.key, 1)),
                             self.codec_seed)
        p0 = self.make_params(self.key)
        self.prog["update_norms"] = np.asarray(_diff_norms(state["params"],
                                                           p0))
        del p0
        # AdamW's first moment after one step is (1 - b1) g
        self.prog["m"] = jax.device_get(state["opt_state"][0])
        self.prog["loss"] = float(m["loss"])
        state, m = self.step(state, self.batch(synthetic.step_key(self.key, 2)),
                             self.codec_seed)
        jax.block_until_ready(m["loss"])
        self.state = state
        self.next_step = 3

    # ------------------------------------------------------------- window
    def window(self, seconds: float, span):
        state = self.state
        losses = []
        pending = None
        steps = 0
        t0 = time.perf_counter()
        while True:
            with span("bench.batch"):
                batch = self.batch(synthetic.step_key(self.key,
                                                      self.next_step))
            with span("bench.step"):
                state, m = self.step(state, batch, self.codec_seed)
            self.next_step += 1
            steps += 1
            losses.append(m["loss"])
            if pending is not None:
                with span("bench.wait"):
                    pending.block_until_ready()
            pending = m["loss"]
            if time.perf_counter() - t0 >= seconds:
                break
        with span("bench.wait"):
            jax.block_until_ready(state)
        elapsed = time.perf_counter() - t0
        self.state = state
        lv = np.asarray(jax.device_get(losses), np.float64)
        return {"attempted": steps, "failed": int(np.sum(~np.isfinite(lv))),
                "elapsed_s": elapsed, "steps": steps,
                "tokens": steps * self.tokens_per_step}

    def end_to_end(self, w):
        return {"train_tokens_per_s": common.metric(
            w["tokens"] / w["elapsed_s"], "tokens/s")}

    # -------------------------------------------------------------- check
    def free(self):
        self.state = None
        gc.collect()

    def _rows(self, matmul, rows):
        """(loss, clipped gradient) of each of ``rows`` of step 1's batch,
        in the reference at ``matmul``, one row after another."""
        tokens = self.batch(synthetic.step_key(self.key, 1))["tokens"]
        params = self.make_params(self.key)
        for r in rows:
            yield reference.loss_and_grad(self.sizes, params,
                                          tokens[r:r + 1], matmul, self.clip)

    def _side(self, pairs, noise):
        loss, acc, n = 0.0, None, 0
        for row_loss, g in pairs:
            loss += float(row_loss)
            acc = g if acc is None else reference.add(acc, g)
            n += 1
        ghat = reference.with_noise(acc, 1.0 / n, self.sigma,
                                    common.name_key(self.key, noise))
        o = self.opt
        upd = reference.adamw_first_update(ghat, lr=o["lr"], b1=o["b1"],
                                           b2=o["b2"], eps=o["eps"])
        return {"loss": loss / n, "ghat": ghat,
                "update_norms": np.asarray(_leaf_norms(upd))}

    def side(self, matmul="f32", rows=None, noise="control"):
        """Step 1 of the reference at ``matmul`` over ``rows`` (all by
        default), put in the program's place: its own N(0, sigma^2) draw
        named ``noise`` is added to the mean gradient.  Returns the loss,
        the decoded gradient and each leaf's change of parameters."""
        rows = range(self.rows) if rows is None else rows
        return self._side(self._rows(matmul, rows), noise)

    def reference_rows(self):
        """The reference's step 1: its loss, each row's per-leaf centred
        gradient (bfloat16) and their Gram matrix, and each leaf's change
        under its own N(0, sigma^2) draw."""
        rows = []

        def tap():
            for pair in self._rows("f32", range(self.rows)):
                rows.append(_centre_bf16(pair[1]))
                yield pair

        ref = self._side(tap(), "reference")
        del ref["ghat"]
        ref["rows"] = rows
        ref["gram"] = np.array([[float(_dot(a, b)) for b in rows]
                                for a in rows])
        return ref

    def compare(self, side, ref):
        """The compared numbers of one side (the program, the control or a
        fault) against the reference."""
        ghat = side["ghat"]
        b = np.array([float(_dot(ghat, r)) for r in ref["rows"]])
        w = np.linalg.solve(ref["gram"], b)
        u_gap, ui = norm_gap(side["update_norms"], ref["update_norms"])
        common.log(f"row weights x rows {list(w * len(w))}; loss "
                   f"{side['loss']} reference {ref['loss']}; worst "
                   f"update leaf {self.paths[ui]}")
        return {
            "row_weight_gap": float(np.max(np.abs(w * len(w) - 1.0))),
            "update_norm_gap": u_gap,
        }

    def program_side(self):
        m = jax.device_put(self.prog["m"], jax.devices()[0])
        return {"loss": self.prog["loss"],
                "ghat": reference.scale(m, 1.0 / (1.0 - self.opt["b1"])),
                "update_norms": self.prog["update_norms"]}

    def check(self):
        ref = self.reference_rows()
        return self.compare(self.program_side(), ref)
