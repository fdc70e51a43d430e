"""Serving cells: one ``InferenceEngine`` on one chip under an open-loop
trace on the real clock.  The runner drives ``engine.submit`` /
``engine.step`` itself (the program's ``run_load`` drains until empty and
has no window), stamps each request with the time it was DUE, and does
its own arithmetic over all requests due in the window.

After the window closes the engine is stepped on until what is in flight
has finished (late is late, not wrong), then freed; the comparison runs
the plain reference once over a sample of finished requests, the longest
among them, and reads the widest gap by which a served token's logit
lies below the reference's best.
"""

from __future__ import annotations

import time

import numpy as np


def build(env):
    """(model, EngineConfig) of this cell."""
    from distributeddataparallel_tpu.models.transformer import TransformerLM
    from distributeddataparallel_tpu.serving.engine import EngineConfig

    from benchmarks.harness import load_module

    train = load_module("kinds", "train")
    model = TransformerLM(train.model_config(env))
    return model, EngineConfig(**env["traffic"]["engine"])


def param_shapes(model, dtype):
    import jax

    from benchmarks.harness import load_module

    shapes = load_module("kinds", "train").param_shapes(model)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, dtype), shapes
    )


class Session:
    def __init__(self, env):
        import jax
        from jax.sharding import SingleDeviceSharding

        from distributeddataparallel_tpu.serving.engine import InferenceEngine

        from benchmarks import traffic
        from benchmarks.harness import make_weights

        t = env["traffic"]
        self.env = env
        self.spans = env["spans"]
        self.seed = env["seed"]
        self.device = env["devices"][0]
        mark = env.get("mark", lambda what: None)
        model, self.ecfg = build(env)
        self.cfg = model.cfg
        self.shapes = param_shapes(model, self.cfg.dtype)
        with jax.default_device(self.device):
            params = make_weights(
                self.shapes, self.seed, self.cfg.num_layers, self.cfg.dtype,
                SingleDeviceSharding(self.device),
            )
            jax.block_until_ready(params)
            mark("weights made on the device")
            self.engine = InferenceEngine(model, params, self.ecfg)
        del params
        mark("engine built")
        self.trace = traffic.make_trace(
            t["requests"], self.seed, env["window_s"], self.cfg.vocab_size
        )
        # warm the cell's own two programs (a prompt of two chunks, then
        # decode) and nothing else
        warm = np.arange(self.ecfg.prefill_chunk + 2, dtype=np.int32)
        self.engine.submit(warm % self.cfg.vocab_size, 3)
        self.engine.run()
        self.engine.completed.clear()
        mark("prefill and decode programs warm")
        self.detail = bool(env["spans"].annotate)
        self.steps: list = []   # per step, traced runs only
        self.requests: list = []

    def _all_requests(self) -> list:
        sch = self.engine.scheduler
        return (list(self.engine.completed.values())
                + list(sch.running.values()) + list(sch.prefilling)
                + list(sch.waiting))

    def _snapshot(self):
        sch = self.engine.scheduler
        return {
            r.rid: (r.prefilled, len(r.generated), r.next_pos)
            for r in list(sch.running.values()) + list(sch.prefilling)
        }

    def measure(self, seconds: float) -> dict:
        engine, spans, trace = self.engine, self.spans, self.trace
        slots = self.ecfg.num_slots
        self.spans.records.clear()
        occupancy = []
        rids = []
        i = 0
        t0 = time.monotonic()
        while True:
            now = time.monotonic() - t0
            if now >= seconds:
                break
            if i < len(trace) and trace[i]["arrival_s"] <= now:
                with spans.span("submit"):
                    while i < len(trace) and trace[i]["arrival_s"] <= now:
                        r = trace[i]
                        rids.append(engine.submit(
                            r["prompt"], r["max_new_tokens"],
                            arrival_s=t0 + r["arrival_s"],
                        ))
                        i += 1
            if engine.has_work():
                before = self._snapshot() if self.detail else None
                with spans.span("step"):
                    stats = engine.step()
                if stats["n_active"] or stats["prefill_chunks"]:
                    occupancy.append(stats["n_active"] / slots)
                if self.detail:
                    self._record_step(before)
            else:
                with spans.span("idle-wait"):
                    time.sleep(0.0002)
        t_close = time.monotonic()
        window_s = t_close - t0
        produced = sum(len(r.generated) for r in self._all_requests())

        # what is in flight finishes; nothing new is submitted
        with spans.span("drain"):
            deadline = t_close + float(self.env["traffic"]["drain_seconds"])
            while engine.has_work() and time.monotonic() < deadline:
                engine.step()

        reqs = {r.rid: r for r in self._all_requests()}
        self.requests = [reqs[rid] for rid in rids]
        due = len(trace)
        ttft, tpot, queue = [], [], []
        tokens_done = 0
        for r in self.requests:
            first = r.first_token_s
            if first is not None and first <= t_close:
                ttft.append(1e3 * (first - r.arrival_s))
            if r.admit_s is not None and r.admit_s <= t_close:
                queue.append(1e3 * (r.admit_s - r.arrival_s))
            if r.done_s is not None and r.done_s <= t_close:
                tokens_done += len(r.generated)
                if len(r.generated) > 1:
                    tpot.append(
                        1e3 * (r.done_s - first) / (len(r.generated) - 1)
                    )
        # due but never submitted, or no first token by the close: missing
        ttft += [float("inf")] * (due - len(ttft))
        from benchmarks.harness import percentile

        ttft_p90 = percentile(ttft, 90)
        if ttft_p90 == float("inf"):
            ttft_p90 = 1e3 * window_s  # the tail never answered in the window
        finished = [r for r in self.requests if r.done_s is not None]
        return {
            "attempted": due,
            "failed": due - len(finished),
            "window_s": window_s,
            "end_to_end": {
                "serve_ttft_p90_ms": ttft_p90,
                "serve_tpot_p90_ms": percentile(tpot, 90) if tpot
                else 1e3 * window_s,
                "serve_tokens_s": tokens_done / window_s,
                "serve_tokens_produced_s": produced / window_s,
            },
            "tpot_ms": tpot,
            "queue_wait_ms": queue,
            "occupancy": occupancy,
            "steps_detail": self.steps,
            "missing_first_token": sum(1 for x in ttft if x == float("inf")),
            "submitted": len(rids),
        }

    def _record_step(self, before: dict) -> None:
        """What this step processed: prefill chunks as (start, n) and the
        context length of every decoded token."""
        after = self._snapshot()
        for rid, req in self.engine.completed.items():
            if rid in before and rid not in after:
                after[rid] = (req.prefilled, len(req.generated), req.next_pos)
        chunks, contexts = [], []
        for rid, (pre0, gen0, pos0) in before.items():
            pre1, gen1, _ = after.get(rid, (pre0, gen0, pos0))
            if pre1 > pre0:
                chunks.append((pre0, pre1 - pre0))
            # the first token comes out of the last prefill chunk; every
            # further token is one row of the decode program
            decoded = (gen1 - gen0) - (1 if gen0 == 0 and gen1 > 0 else 0)
            contexts += [pos0 + j for j in range(max(decoded, 0))]
        for rid, (pre1, gen1, _) in after.items():
            if rid not in before:  # admitted and prefilled in this step
                if pre1 > 0:
                    chunks.append((0, pre1))
                if gen1 > 1:  # a one-chunk prompt decodes in the same step
                    contexts.append(pre1)
        self.steps.append({"chunks": chunks, "contexts": contexts})

    def release(self) -> None:
        self.finished = [
            (np.asarray(r.prompt), list(r.generated))
            for r in self.requests if r.done_s is not None
        ]
        self.engine = None
        self.requests = []

    def sample(self) -> list:
        """Finished requests to compare: the longest, and others drawn
        from the seed."""
        n = int(self.env["traffic"]["check_requests"])
        if not self.finished:
            return []
        order = sorted(
            range(len(self.finished)),
            key=lambda i: -(self.finished[i][0].size
                            + len(self.finished[i][1])),
        )
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 0xC4EC])
        rest = [int(i) for i in rng.permutation(order[1:])[: n - 1]]
        return [self.finished[i] for i in [order[0]] + rest]

    def reference_weights(self):
        import jax
        from jax.sharding import SingleDeviceSharding

        from benchmarks.harness import flatten, make_weights

        with jax.default_device(self.device):
            return flatten(make_weights(
                self.shapes, self.seed, self.cfg.num_layers, self.cfg.dtype,
                SingleDeviceSharding(self.device),
            ))

    def pad_to(self) -> int:
        r = self.env["traffic"]["requests"]
        longest = r["prompt_len"][1] + r["output_len"][1]
        return min(-(-longest // 128) * 128, self.cfg.max_seq_len)

    def check(self) -> list:
        import jax

        from benchmarks.reference import gpt2

        limit = self.env["traffic"]["limits"].get("served_logit_gap")
        sample = self.sample()
        if not sample:
            return [("served_logit_gap", float("inf"), limit)]
        w = self.reference_weights()
        worst = 0.0
        served = 0
        with jax.default_device(self.device):
            for prompt, generated in sample:
                gaps, _ = gpt2.served_gaps(w, prompt, generated, self.pad_to())
                worst = max(worst, float(np.max(gaps)))
                served += len(generated)
        self.checked_tokens = served
        return [("served_logit_gap", worst, limit)]


def setup(env) -> Session:
    return Session(env)
