"""The ``lm`` system: a language model from the program's registry served
by its ``Scheduler`` through the continuous-batching engine.

The weights come from the configuration's reference module, drawn from
the seed on the device in one jitted call, in the layout the program
consumes.  The configuration's family module (``bench/families``) checks
that the program's architecture is the configuration.  Output tokens are counted as the engine produces them: the
stepper's calls are wrapped, so each token is stamped by the
benchmark's clock when the call that made it returns.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

from benchlib import files, traffic as gen

class TokenClock:
    """Times every output token by the stepper call that made it: one per
    row at prefill, one per live slot at each step.  A call's tokens are
    spread evenly over the call, so a window that cuts a step counts the
    share of it inside."""

    def __init__(self, stepper, clock=time.monotonic):
        self.clock = clock
        self.calls: List[tuple] = []       # (start, end, tokens, prefill)
        self.live = 0
        self._lock = threading.Lock()
        prefill, insert, step, finish = (stepper.prefill, stepper.insert,
                                         stepper.step, stepper.finish)

        def _prefill(spec):
            t = self.clock()
            rows = prefill(spec)
            self._add(t, len(rows), True)
            return rows

        def _insert(state, slot, row_state):
            out = insert(state, slot, row_state)
            with self._lock:
                self.live += 1
            return out

        def _step(state):
            t = self.clock()
            out = step(state)
            self._add(t, self.live, False)
            return out

        def _finish(state, slot, first, collected):
            with self._lock:
                self.live -= 1
            return finish(state, slot, first, collected)

        stepper.prefill, stepper.insert = _prefill, _insert
        stepper.step, stepper.finish = _step, _finish

    def _add(self, t: float, n: int, prefill: bool) -> None:
        end = self.clock()
        with self._lock:
            self.calls.append((t, end, n, prefill))

    def _count(self, t0: float, t1: float, prefill_only: bool) -> float:
        total = 0.0
        with self._lock:
            calls = list(self.calls)
        for a, b, n, pre in calls:
            if prefill_only and not pre:
                continue
            inside = min(b, t1) - max(a, t0)
            if b <= a:
                total += n if t0 <= b < t1 else 0.0
            elif inside > 0:
                total += n * inside / (b - a)
        return total

    def tokens_in(self, t0: float, t1: float) -> float:
        """Output tokens emitted in ``[t0, t1)``."""
        return self._count(t0, t1, False)

    def prefills_in(self, t0: float, t1: float) -> float:
        """Prompt rows prefilled in ``[t0, t1)``."""
        return self._count(t0, t1, True)


class LM:
    def __init__(self, cfg: dict, tr: dict, seed: int, ref, log):
        import jax

        from repro.configs import registry
        from repro.core.hybrid_executor import detect_platform
        from repro.serve.scheduler import Scheduler
        from repro.workloads import requests as adapters

        self.cfg, self.tr, self.ref, self.log, self.seed = (cfg, tr, ref,
                                                            log, seed)
        family = files.module("families", cfg["family"])
        self.arch = registry.get(cfg["arch"])
        if cfg.get("resize_program"):
            # the benchmark's own tests run the program at a tiny size
            self.arch = family.resized(self.arch, cfg)
        family.check_program(cfg, self.arch)
        self.prompt_len = int(tr["prompt_len"])
        self.new_tokens = int(tr["new_tokens"])
        t = time.perf_counter()
        self.params = jax.jit(lambda k: ref.weights(k, cfg))(
            jax.random.key(int(seed) % (1 << 31)))
        jax.block_until_ready(self.params)
        self.n_params = int(sum(x.size for x in jax.tree.leaves(self.params)))
        log(f"# weights {self.n_params / 1e6:.1f}M in "
            f"{time.perf_counter() - t:.1f}s")
        self.groups, _ = detect_platform()
        self.wl = adapters.make_continuous_lm_adapter(
            self.arch, self.params, prompt_len=self.prompt_len,
            new_tokens=self.new_tokens, n_slots=int(cfg["n_slots"]),
            warm_background=False)
        spec = adapters.make_request(self.wl, self._payload(-1, 0))
        self.tokens_stepper = spec.stepper
        self.tokens = TokenClock(spec.stepper)
        self.sched = Scheduler(groups=self.groups, policy=cfg["policy"])
        t = time.perf_counter()
        self.sched.submit(self.wl, self._payload(-1, 0)).result(timeout=900)
        plan = self.sched.engine_placements[self.wl]
        self.plan = (plan.prefill_group, plan.decode_group)
        log(f"# lm warm-up {time.perf_counter() - t:.1f}s prefill="
            f"{plan.prefill_group} decode={plan.decode_group}")
        self._lock = threading.Lock()
        self.answers: List[tuple] = []     # (prompt, served tokens)
        self.tokens.calls.clear()

    def _payload(self, client: int, k: int) -> dict:
        return {"prompt": gen.prompt(self.seed, client, k, self.prompt_len,
                                     self.cfg["vocab_size"])}

    # -- traffic ---------------------------------------------------------
    def item(self, client: int, k: int):
        return self._payload(client, k)

    def submit(self, item, on_done) -> None:
        fut = self.sched.submit(self.wl, dict(item))

        def done(f):
            exc = f.exception(timeout=0)
            if exc is None:
                with self._lock:
                    self.answers.append((item["prompt"],
                                         np.asarray(f.result(timeout=0))))
            on_done(exc)

        fut.add_done_callback(done)

    def counters(self) -> Dict[str, float]:
        eng = {}
        for e in getattr(self.sched, "_engines", {}).values():
            eng = e.snapshot()
        return {"engine_" + k: v for k, v in eng.items()
                if isinstance(v, (int, float))}

    def shutdown(self) -> None:
        self.sched.shutdown()
        self.params = None

    # -- correctness -----------------------------------------------------
    def sample(self) -> List[tuple]:
        """The finished requests compared: ``cfg['check_requests']`` of
        them drawn from the seed (all requests are equally long)."""
        with self._lock:
            answers = list(self.answers)
        n = min(int(self.cfg["check_requests"]), len(answers))
        pick = gen.rng(self.seed, 40).choice(len(answers), n, replace=False)
        return [answers[i] for i in sorted(pick)]

    def check(self, control: str = "") -> List[tuple]:
        """``(number, value, limit)`` for each number the reference
        limits: the served tokens' logits against the reference's,
        teacher-forced over each sampled prompt and its served tokens
        (``ref.token_gaps``).  With ``control`` the reference at the
        control's precision picks the tokens instead, and every number
        the reference reads is returned."""
        import jax

        limits = self.ref.LIMITS
        sample = self.sample()
        if not sample:
            return [(n, float("inf"), lim) for n, lim in limits.items()]
        params = jax.jit(lambda k: self.ref.weights(k, self.cfg))(
            jax.random.key(int(self.seed) % (1 << 31)))
        seqs = np.stack([np.concatenate([p[0], t[0][:-1]])
                         for p, t in sample])
        served = np.stack([t[0] for _, t in sample])
        low = self.ref.CONTROLS[control] if control else {}
        read = self.ref.token_gaps(params, seqs, served, self.cfg,
                                   self.prompt_len, **low)
        self.log(f"# token gaps{' ' + control if control else ''} over "
                 f"{served.size} served tokens: "
                 + " ".join(f"{k} {v!r}" for k, v in read.items()))
        if control:
            return [(n, v, limits.get(n, float("inf")))
                    for n, v in read.items()]
        return [(n, read[n], lim) for n, lim in limits.items()]


def setup(cfg, tr, seed, ref, log):
    return LM(cfg, tr, seed, ref, log)
