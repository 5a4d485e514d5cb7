"""Image-classification cells: back-to-back batches through the program's
ResNet (``get_resnet(arch, epitome)``, prepacked weights, ``apply``).

Weights and images are made on the device in one jitted call each, from
the seed.  A batch counts when its logits are ready; the window closes
when the last batch begun inside ``--seconds`` is ready, so the rate is
all images of the window over all of its time, with no partial batch.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, Optional

from bench import loadgen, work


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.done_at = []          # completion time of each batch
        self.failed = 0
        self.t0 = self.t_end = 0.0
        self.counters = {}

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.configs import get_resnet
        prog = self.cfg["program"]
        self.model = get_resnet(prog["arch"], prog["epitome"])
        key = jax.random.PRNGKey(loadgen.key_seed(self.seed))
        self.init_key, k_img = jax.random.split(key)
        model = self.model
        self.params = jax.jit(lambda k: model.prepack(model.init(k)))(
            self.init_key)
        B, S, P = self.traffic["batch"], self.cfg["image"], self.traffic["pool"]
        pool = jax.jit(lambda k: jax.random.normal(
            k, (P, B, S, S, 3), jnp.float32))(k_img)
        self.batches = [pool[i] for i in range(P)]
        del pool
        self.apply = jax.jit(model.apply)
        self.apply(self.params, self.batches[0]).block_until_ready()
        self.pick = int(loadgen.rng_for(self.seed, "check").integers(P))
        self.kept = None

    def snapshot(self, tag: str) -> None:
        self.counters[tag] = {"t": time.perf_counter()}

    def run_window(self, seconds: float, slice_) -> None:
        import jax
        P = self.traffic["pool"]
        self.t0 = t0 = time.perf_counter()
        end = t0 + seconds
        slice_.open(t0)
        i = 0
        while time.perf_counter() < end:
            with jax.profiler.TraceAnnotation("bench.batch"):
                y = self.apply(self.params, self.batches[i % P])
                y.block_until_ready()
            self.done_at.append(time.perf_counter())
            if i % P == self.pick:
                self.kept = y
            i += 1
            slice_.tick()
        self.t_end = self.done_at[-1] if self.done_at else time.perf_counter()
        slice_.close()

    def end_to_end(self) -> Dict[str, Optional[float]]:
        n = len(self.done_at) * self.traffic["batch"]
        return {"images_per_s": n / (self.t_end - self.t0), "images": n,
                "batches": len(self.done_at)}

    def attempted(self) -> int:
        return len(self.done_at) * self.traffic["batch"]

    def slice_work(self, t_a: float, t_b: float, peaks: dict
                   ) -> Dict[str, float]:
        """Batches whose logits became ready between two instants (the
        slice opens and closes between batches), their useful operations
        and the least time of their epitome-kernel calls."""
        n = sum(t_a <= t <= t_b for t in self.done_at)
        B = self.traffic["batch"]
        return {"batches": n, "images": n * B,
                "useful_ops": n * B * work.resnet_image_ops(self.cfg),
                "kernel_least_s": n * work.calls_least_seconds(
                    work.resnet_epitome_calls(self.cfg, B), peaks)}

    def release(self) -> None:
        import jax
        self.images = self.batches[self.pick]
        self.logits = None if self.kept is None else jax.device_get(self.kept)
        self.params = self.batches = self.kept = self.apply = self.model = None
        gc.collect()

    def check(self, control: bool = False):
        """The numbers of ``check.image_errors``; those the configuration
        gives a limit are compared, the rest are printed for the record."""
        from bench import check
        if self.logits is None:
            return [("unchecked", 1, 0)]       # no batch finished
        got = check.image_errors(self.cfg, self.init_key, self.images,
                                 self.logits, control=control)
        lim = self.cfg["check"]
        return [(k, v, lim.get(k)) for k, v in got.items()]
