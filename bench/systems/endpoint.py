"""A model endpoint served from a HotSwap pool (``"system": "endpoint"``).

The harness makes the weights on the device from the seed, in the dtype the
configuration serves, and registers them as one pool image. Replicas come up
through the program's ``ServingEngine.from_pool`` and serve greedily through
``submit`` and ``step``. The harness stamps each request's arrival and the
moment its first token is visible after a ``step`` returns.

The traffic mix names its request generator (``kind``: a module
``bench/traffic/<kind>.py`` with ``requests(mix, seed, vocab_size)``) and its
arrival loop (``loop``: a module ``bench/loops/<loop>.py`` with
``run(cell, window, requests)``), so a new mix is a new file.
"""
from __future__ import annotations

import importlib
import tempfile
from typing import Dict, List, Optional

import numpy as np

from bench.lib import footprint
from bench.lib.counts import DenseShape
from bench.lib.record import Recorder, now
from bench.models import dense
from bench.systems.common import (Check, FailureLog, Invocation, Window,
                                  leaves_bytes_differing)

# lower precisions the check can put in the program's place: the whole
# reference in bfloat16, or its weights rounded to int8 or fp8
CONTROLS = ("bfloat16", "int8", "float8_e4m3fn")


class Cell:
    def __init__(self, config: Dict, mix: Dict, seed: int, rec: Recorder):
        self.config, self.mix, self.seed, self.rec = config, mix, seed, rec
        self.shape = DenseShape.from_config(config)
        self.dtype = config["served_dtype"]
        self.failures = FailureLog()
        self.finished: List[Dict] = []
        # parameters of the first replica and of the latest one after it
        self.kept_params: List = []
        self.engine = None
        # (engine, rid of each slot, logits) of every decode step, and the
        # last-position logits of each admission by (engine, rid)
        self.step_logits: List = []
        self.prefill_logits: Dict = {}
        self._engines = 0
        self._admitting = None

    def _weights(self):
        return dense.make_weights(self.shape, self.seed, self.dtype)

    def _arch(self):
        from repro.models.config import GLOBAL_ATTN, ArchConfig
        c, s = self.config, self.shape
        rc = dense.reference_config(c)
        return ArchConfig(
            name=c["name"], family="dense", n_layers=s.n_layers,
            d_model=s.d_model, n_heads=s.n_heads, n_kv_heads=s.n_kv_heads,
            d_ff=s.d_ff, vocab_size=s.vocab_size, head_dim=s.head_dim,
            attn_pattern=(GLOBAL_ATTN,), qkv_bias=s.qkv_bias, mlp="swiglu",
            rope_theta=rc["theta"], norm_eps=rc["eps"],
            tie_embeddings=s.tie_embeddings)

    # ------------------------------------------------------------------ set-up
    def setup(self) -> None:
        """The pool image, and one replica that serves one prompt of every
        length of the mix with every slot filled, so that each shape the
        window uses is compiled or loaded from the cache. With the mix's
        ``warm_replica`` that replica stays up for the window as
        ``self.engine``."""
        from repro.core import DependencyManager, RestorePolicy
        from repro.serving import ServeConfig

        m = self.mix
        self.arch = self._arch()
        self.policy = RestorePolicy(m["policy"])
        self.scfg = ServeConfig(max_slots=m["slots"], max_seq_len=m["max_seq_len"],
                                max_new_tokens=m["max_new_tokens"])
        self._disk = tempfile.TemporaryDirectory(prefix="bench-pool-")
        self.mgr = DependencyManager(disk_dir=self._disk.name)
        self.image_id = f"arch-{self.config['name']}"
        self._keep_prefill()
        self.mgr.register_image(self.image_id, self.config["name"], self._weights)
        lens = [m["prompt_lengths"][i % len(m["prompt_lengths"])]
                for i in range(max(m["slots"], len(m["prompt_lengths"])))]
        rng = np.random.default_rng([self.seed, 3])
        engine = self.bring_up()
        for n in lens:
            engine.submit(rng.integers(0, self.shape.vocab_size, n, dtype=np.int32))
        engine.run_until_done()
        self.engine = engine if m.get("warm_replica") else None

    def bring_up(self):
        """A replica from the pool, with the harness's spans around admission
        and the logits of each admission and decode step kept for the check
        (the work is the program's own)."""
        from repro.serving import ServingEngine
        engine = ServingEngine.from_pool(self.mgr, self.image_id, self.arch,
                                         self.scfg, policy=self.policy)
        if len(self.kept_params) < 2:
            self.kept_params.append(engine.params)
        else:
            self.kept_params[1] = engine.params
        eid = self._engines = self._engines + 1
        admit, serve_step, submit = engine._admit, engine._serve_step, engine.submit
        queued: List[int] = []

        def traced_admit():
            self._admitting = (eid, queued)
            with self.rec.span("bench.admission"):
                admit()

        def kept_serve_step(params, state, tok):
            logits, state = serve_step(params, state, tok)
            rids = [None if r is None else r.rid for r in engine._slots]
            self.step_logits.append((eid, rids, logits))
            return logits, state

        def kept_submit(prompt, max_new_tokens=None):
            rid = submit(prompt, max_new_tokens)
            queued.append(rid)
            return rid
        engine._admit, engine._serve_step = traced_admit, kept_serve_step
        engine.submit = kept_submit
        engine.bench_id = eid
        return engine

    def _keep_prefill(self) -> None:
        """Wraps the engine module's ``forward`` so that each admission's
        last-position logits are kept. Admission takes requests in the order
        they were submitted, so the n-th prefill of an engine is its n-th
        submitted request."""
        import repro.serving.engine as engine_mod
        forward = self._program_forward = engine_mod.forward

        def kept_forward(*args, **kwargs):
            out = forward(*args, **kwargs)
            eid, queued = self._admitting
            rid = queued.pop(0)
            self.prefill_logits[(eid, rid)] = out[0][0, -1]
            return out
        engine_mod.forward = kept_forward

    def _restore_forward(self) -> None:
        import repro.serving.engine as engine_mod
        engine_mod.forward = self._program_forward

    # ------------------------------------------------------------------ window
    def run_window(self, seconds: float) -> Window:
        traffic = importlib.import_module(f"bench.traffic.{self.mix['kind']}")
        loop = importlib.import_module(f"bench.loops.{self.mix['loop']}")
        win = Window(start=now(), seconds=seconds)
        loop.run(self, win, traffic.requests(self.mix, self.seed,
                                             self.shape.vocab_size))
        win.end = now()
        return win

    def track(self, engine, inv: Invocation, prompt) -> Dict:
        """Submits ``prompt`` to ``engine`` as invocation ``inv``."""
        rid = engine.submit(prompt)
        return {"rid": rid, "inv": inv, "prompt": prompt, "req": None}

    def step(self, engine, win: Window, live: List[Dict]) -> List[Dict]:
        """One engine step; stamps first tokens; keeps and returns the tracked
        requests it finished."""
        t0 = now()
        with self.rec.span("bench.engine_step") as span:
            active = engine.step()
        t1 = now()
        in_slots = {r.rid: r for r in engine._slots if r is not None}
        admitted, done, contexts = [], [], []
        for t in live:
            req = in_slots.get(t["rid"]) or engine.completed.get(t["rid"])
            if req is None:
                continue
            if t["inv"].first is None:
                t["inv"].first = t1
                t["req"] = req
                admitted.append(len(t["prompt"]))
            if len(req.tokens) >= 2:
                contexts.append(len(t["prompt"]) + len(req.tokens) - 1)
            if t["rid"] in engine.completed:
                done.append(t)
                self.finished.append({"prompt": t["prompt"], "engine": engine.bench_id,
                                      "rid": t["rid"], "tokens": list(req.tokens)})
        span.attrs["admitted"] = len(admitted)
        win.token_events.append((t0, t1, active + len(admitted)))
        win.steps.append({"t0": t0, "t1": t1, "span": span.index,
                          "admitted": admitted, "contexts": contexts})
        return done

    # ------------------------------------------------------------------ after
    def pool_bytes(self) -> int:
        return footprint.pool_bytes([self.mgr], self._disk.name)

    def release(self) -> None:
        """Frees the replicas and the pool; keeps on the host the parameters
        of the first and the latest replica and the logits of the requests
        the check reads."""
        import jax
        self._restore_forward()
        V = self.shape.vocab_size
        for r in self.sample():
            key = (r["engine"], r["rid"])
            steps = [lg[rids.index(r["rid"])] for eid, rids, lg in self.step_logits
                     if eid == r["engine"] and r["rid"] in rids]
            rows = [self.prefill_logits[key][:V]] + steps[:len(r["tokens"]) - 1]
            r["logits"] = np.stack([np.asarray(x[:V]) for x in rows])
        self.step_logits, self.prefill_logits = [], {}
        self.kept_params = [jax.tree.map(np.asarray, p) for p in self.kept_params]
        self.engine = None
        del self.mgr
        self._disk.cleanup()

    def sample(self) -> List[Dict]:
        """Finished requests to compare, drawn from the seed: the one with the
        longest prompt, and others up to the mix's ``check_requests``."""
        done = self.finished
        if not done:
            return []
        longest = max(range(len(done)), key=lambda i: len(done[i]["prompt"]))
        rest = [i for i in range(len(done)) if i != longest]
        rng = np.random.default_rng([self.seed, 4])
        k = min(self.mix["check_requests"] - 1, len(rest))
        pick = [longest] + sorted(rng.choice(rest, size=k, replace=False).tolist())
        return [done[i] for i in pick]

    def check(self, limits: Dict[str, float],
              control: Optional[str] = None) -> List[Check]:
        """Numbers compared with their limits.

        ``params_bytes_wrong``: bytes of the first and the latest replica's
        parameters that differ from the weights made from the seed.
        ``requests_short``: finished requests with fewer tokens than asked.
        Over the sampled requests: ``tokens_not_greedy``, served tokens that
        are not the argmax of the logits the program computed for them;
        ``token_gap``, the widest gap by which a served token's logit lies
        below the reference's best at its position; ``logit_rel_err``, the
        largest relative L2 distance, over the real vocabulary, between the
        program's logits of a served position (its prefill's last position,
        then each decode step through the cache) and the reference's.

        With ``control`` (one of :data:`CONTROLS`) the reference computed in
        that precision takes the program's place: its weights are compared
        byte for byte, its served tokens are those it puts first."""
        rows = self.sample()
        w = self._weights()
        seed_weights = jax_host(w)
        if control is None:
            wrong = sum(leaves_bytes_differing(p, seed_weights)
                        for p in self.kept_params)
            short = sum(1 for r in self.finished
                        if len(r["tokens"]) != self.mix["max_new_tokens"])
            greedy = sum(int(np.count_nonzero(r["logits"].argmax(-1)
                                              != np.asarray(r["tokens"])))
                         for r in rows)
            extra = [Check("requests_short", short, 0),
                     Check("tokens_not_greedy", greedy, 0)]
        else:
            wc = control_weights(w, control)
            wrong = leaves_bytes_differing(jax_host(wc), seed_weights)
            extra = []
        gap, rel = self._compare(w, rows, control)
        return [Check("params_bytes_wrong", wrong, 0), *extra,
                Check("token_gap", gap, limits["token_gap"]),
                Check("logit_rel_err", rel, limits["logit_rel_err"])]

    def _compare(self, w, rows: List[Dict], control: Optional[str]):
        """(widest token gap, largest relative logit error) of the sampled
        requests against the float32 reference, for the program or, with
        ``control``, for the reference in that precision."""
        import jax.numpy as jnp

        if not rows:
            return float("inf"), float("inf")
        rc = dense.reference_config(self.config)
        L, V = self.mix["max_seq_len"], self.shape.vocab_size
        wc, mode = None, "float32"
        if control is not None:
            wc = control_weights(w, control)
            mode = "bfloat16" if control == "bfloat16" else "float32"
        gap = rel = 0.0
        for i in range(0, len(rows), 2):
            block = rows[i:i + 2]
            inp = np.zeros((2, L), np.int32)     # one shape: compiled once
            for b, r in enumerate(block):
                full = np.concatenate([r["prompt"], np.asarray(r["tokens"], np.int32)])
                inp[b, :len(full) - 1] = full[:-1]
            h = dense.forward_hidden(w, jnp.asarray(inp), self.shape, rc["theta"],
                                     rc["eps"])
            hc = None if wc is None else dense.forward_hidden(
                wc, jnp.asarray(inp), self.shape, rc["theta"], rc["eps"], mode=mode)
            for b, r in enumerate(block):
                p, n = len(r["prompt"]), len(r["tokens"])
                ref = np.asarray(dense.logits(w, h[b, p - 1:p - 1 + n]))[:, :V]
                if hc is None:
                    got, toks = r["logits"], np.asarray(r["tokens"])
                else:
                    got = np.asarray(dense.logits(wc, hc[b, p - 1:p - 1 + n],
                                                  mode=mode))[:, :V]
                    toks = got.argmax(-1)
                served = ref[np.arange(n), toks]
                gap = max(gap, float((ref.max(-1) - served).max()))
                err = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
                rel = max(rel, float(err.max()))
        return gap, rel


def control_weights(w, control: str):
    """The seed's weights as the ``control`` precision holds them."""
    import jax
    import jax.numpy as jnp
    if control == "bfloat16":
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16), w)
    return dense.quantize(w, control)


def jax_host(tree):
    import jax
    return jax.tree.map(np.asarray, tree)
