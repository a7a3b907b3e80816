"""The training cells' system under test: SGDTrainer.train fed by a
DevicePrefetcher over a pool of host batches, K steps per dispatch.

Set-up builds ONE trainer, installs weights made from the seed, and drives
it through its first steps by the window's own call and feed: three batches
(which train() runs as single-step dispatches, being fewer than K) and then
one fused K-step dispatch. After each the per-leaf norms the comparison
needs are taken on the device. The window then drives that same trainer."""

from __future__ import annotations

import collections
import gc
import math
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from perfbench import arith, registry, weights
from perfbench.reference import compare, lowprec, optim


class TrainSystem:
    FIRST_STEPS = 3
    PREFETCH_DEPTH = 2   # DevicePrefetcher's default, as the CLI leaves it

    def __init__(self, cell, seed: int, make_cost: Callable, make_optimizer: Callable):
        self.cell, self.seed = cell, int(seed)
        self.cfg, self.wl = cell.config, cell.workload
        self.make_cost, self.make_optimizer = make_cost, make_optimizer
        self.k = int(self.cfg["steps_per_dispatch"])
        if cell.chips != 1:
            raise ValueError("the training harness drives one chip; a cell across "
                             "chips brings its plan and its proof (PERF.md section 7)")
        self.rows = int(self.cfg["rows_per_chip"])
        self.opt = dict(self.cfg["optimizer"])
        self.trainer = None
        self.prog: Dict = {}
        self.shapes: Dict = {}

    # -- set-up ---------------------------------------------------------------
    def _train(self, batches, handler) -> None:
        from paddle_tpu.data.pipeline import DevicePrefetcher

        feed = DevicePrefetcher(
            lambda: iter(batches), prefetch_depth=self.PREFETCH_DEPTH,
            stack_k=int(self.wl["params"].get("stack_k", self.k)),
        )
        self.trainer.train(
            feed, event_handler=handler, steps_per_dispatch=self.k,
            log_period=10 ** 9,
        )

    def check_batches(self) -> List[dict]:
        n = len(self.pool)
        return [self.pool[i % n] for i in range(self.FIRST_STEPS + self.k)]

    def setup(self, say=print) -> None:
        import jax

        from paddle_tpu.nn.graph import reset_name_scope
        from paddle_tpu.trainer import SGDTrainer
        from paddle_tpu.trainer.events import EndIteration

        gen = registry.load_module("traffic", self.wl["generator"])
        self.pool = gen.make_pool(self.wl["params"], self.rows, self.seed)
        self.order = gen.order(self.wl["params"], self.seed)
        reset_name_scope()
        self.trainer = SGDTrainer(
            self.make_cost(self.cfg), self.make_optimizer(self.opt),
            precision=self.cfg["precision"], seed=0,
        )
        self.trainer.init_state(self.pool[0])
        self.shapes = {k: tuple(v.shape) for k, v in self.trainer.state["params"].items()}
        self.trainer.state["params"] = weights.make_weights(
            self.shapes, self.seed, self.cfg.get("weights")
        )
        p0 = compare.snapshot(self.trainer.state["params"])
        prog = {"losses": []}
        keep = {}
        first_grad = jax.jit(lambda first_slots: {
            k: optim.first_gradient(self.opt, v) for k, v in first_slots.items()})

        def handler(ev):
            if not isinstance(ev, EndIteration):
                return
            n = len(prog["losses"])
            params = self.trainer.state["params"]
            if n == 0:
                slots = self.trainer.state["opt"]["slots"]
                grad = first_grad({k: slots[k][0] for k in params})
                prog["grad"] = compare.leaf_norms(grad)
                # kept on the host: the window's memory peak is the program's
                prog["grad_full"] = jax.device_get(grad)
            elif n == self.FIRST_STEPS - 1:
                prog["delta3"] = compare.diff_norms(params, p0)
                keep["p3"] = compare.snapshot(params)
            elif n == self.FIRST_STEPS:
                prog["delta_fused"] = compare.diff_norms(params, keep.pop("p3"))
            prog["losses"].append(float(ev.cost))

        batches = self.check_batches()
        for batch in batches[: self.FIRST_STEPS]:
            # fewer than K batches: train() runs a single-step dispatch
            self._train([batch], handler)
        # two fused dispatches: the second warms what only a pass's second
        # dispatch runs (the pass-cost accumulation); the reference follows
        # the first
        n = len(self.pool)
        more = [self.pool[(len(batches) + i) % n] for i in range(self.k)]
        self._train(batches[self.FIRST_STEPS:] + more, handler)
        if len(prog["losses"]) != self.FIRST_STEPS + 2:
            raise RuntimeError(
                f"expected {self.FIRST_STEPS} single-step dispatches and one "
                f"two fused ones, saw {len(prog['losses'])} dispatches"
            )
        for key in ("grad", "delta3", "delta_fused"):
            prog[key] = compare.to_floats(prog[key])
        self.prog = prog
        say("info: first steps' losses " + " ".join(f"{x:.5f}" for x in prog["losses"]))

    # -- the measured window --------------------------------------------------
    def window(self, seconds: float, profiler, t_process_start: float) -> dict:
        from paddle_tpu.trainer.events import EndIteration

        pending = collections.deque()
        costs: List[float] = []
        stamps: List[float] = []
        n, k = len(self.pool), self.k

        def fetch(ev) -> None:
            costs.append(float(ev.cost))      # blocks until that dispatch ran
            stamps.append(time.perf_counter())

        def handler(ev):
            if not isinstance(ev, EndIteration):
                return
            pending.append(ev)
            if len(pending) > 1:              # run one dispatch ahead, no more
                fetch(pending.popleft())
            if profiler.due(time.perf_counter(), t_end):
                profiler.start()

        def batches():
            i = 0
            while time.perf_counter() < t_end:
                for _ in range(k):
                    yield self.pool[self.order[i % n]]
                    i += 1

        t0 = time.perf_counter()
        t_end = t0 + seconds
        self._train(batches(), handler)
        while pending:
            fetch(pending.popleft())          # the window ends on this fetch
        t1 = time.perf_counter()
        steps = len(costs) * k
        items = steps * self.rows * int(self.cfg["items_per_row"])
        failed = sum(1 for c in costs if not math.isfinite(c)) * k
        per_dispatch = np.diff([t0] + stamps)
        facts = {"window_s": t1 - t0, "steps": steps, "rows": self.rows, "k": k}
        # a traced run's own rate, without the profiler's part of the window
        clean = [s for s in stamps if profiler.t_start is None or s <= profiler.t_start]
        if clean and len(clean) < len(stamps):
            facts["throughput_untraced"] = arith.rate(
                items * len(clean) / len(stamps), clean[-1] - t0, self.cell.chips)
        return {
            "attempted": steps,
            "failed": failed,
            "end_to_end": {
                "throughput": arith.rate(items, t1 - t0, self.cell.chips),
                "setup_s": t0 - t_process_start,
            },
            "facts": facts,
            "info": [
                f"{steps} steps of {self.rows} rows in {t1 - t0:.3f} s; "
                f"median dispatch {1e3 * float(np.median(per_dispatch[1:] if len(per_dispatch) > 1 else per_dispatch)):.1f} ms "
                f"for {k} steps, longest {1e3 * float(per_dispatch.max()):.1f} ms "
                f"(dispatch {int(per_dispatch.argmax())} of {len(per_dispatch)}); "
                f"last cost {costs[-1]:.5f}",
            ],
        }

    def release(self) -> None:
        self.trainer.state = None
        self.trainer = None
        gc.collect()

    # -- the comparison ---------------------------------------------------------
    def reference_readings(self, cast_name: str = "float32", fault: Optional[str] = None) -> dict:
        """The plain reference over the check drive's batches; with `cast_name`
        below float32 it is the control; `fault` plants one of the faults a
        training cell can have in the reference put in the program's place."""
        ref = registry.load_module("reference", self.cfg["reference"])
        loss_fn = ref.make_loss(self.cfg, lowprec.CASTS[cast_name])
        params = weights.make_weights(self.shapes, self.seed, self.cfg.get("weights"))
        batches = self.check_batches()
        if fault == "half_batch":
            half = self.rows // 2
            batches = [{k: v[:half] for k, v in b.items()} for b in batches]
        return compare.follow(loss_fn, params, batches, self.opt, (self.FIRST_STEPS, self.k))

    FAULTS = ("half_batch",)

    def calibrate(self, window_s=0.0, program=True, control=False, faults=False):
        """Readings for perfbench/calibrate.py: the program's numbers against
        the reference; the control's and each fault's in its place."""
        ref = None
        if program:
            self.setup(say=lambda *_: None)
            self.release()
            ref = self.reference_readings()
            yield {"who": "program", "numbers": compare.training_numbers(self.prog, ref),
                   "losses": self.prog["losses"], "ref_losses": ref["losses"]}
        else:
            self._shapes_only()
        ref = ref or self.reference_readings()
        if control:
            ctl = self.reference_readings(self.wl["check"]["control"])
            yield {"who": "control:" + self.wl["check"]["control"],
                   "numbers": compare.training_numbers(ctl, ref), "losses": ctl["losses"]}
        if faults:
            for fault in self.FAULTS:
                bad = self.reference_readings(fault=fault)
                yield {"who": "fault:" + fault,
                       "numbers": compare.training_numbers(bad, ref), "losses": bad["losses"]}

    def _shapes_only(self) -> None:
        """Parameter names and shapes and the batch pool, without building a
        trainer's state on the device."""
        import jax

        from paddle_tpu.nn.graph import Network, reset_name_scope

        gen = registry.load_module("traffic", self.wl["generator"])
        self.pool = gen.make_pool(self.wl["params"], self.rows, self.seed)
        reset_name_scope()
        net = Network([self.make_cost(self.cfg)])
        small = {k: v[:2] for k, v in self.pool[0].items()}
        shapes = jax.eval_shape(
            lambda: net.init(jax.random.PRNGKey(0), small, train=True)[0]
        )
        self.shapes = {k: tuple(v.shape) for k, v in shapes.items()}

    def verify(self, say=print) -> Dict[str, tuple]:
        ref = self.reference_readings()
        readings = compare.training_readings(self.prog, ref)
        limits = self.wl["check"]["limits"]
        say("info: reference losses " + " ".join(f"{x:.5f}" for x in ref["losses"]))
        still = len(ref["grad"]) - len(compare.moving_leaves(ref["grad"]))
        say(f"info: {still} of {len(ref['grad'])} leaves have a gradient under a thousandth "
            "of the median leaf's and are left out of the change gaps")
        for name, (value, leaf) in readings.items():
            at = f" on {leaf}" if leaf else ""
            note = "" if name in limits else " (reported, not compared)"
            say(f"info: {name} {value:.6g}{at}{note}")
        return self.judge({k: v for k, (v, _) in readings.items()})

    def judge(self, numbers: Dict[str, float]) -> Dict[str, tuple]:
        """Each number the cell compares beside its limit: what harness.decide
        takes, for the program's readings, the control's and a fault's alike."""
        limits = self.wl["check"]["limits"]
        return {k: (float(numbers[k]), float(limits[k])) for k in limits}
