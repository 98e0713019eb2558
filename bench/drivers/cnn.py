"""Drive a CNN through the program's PIM executor as a client.

Set-up builds the network from the seed and programs every conv and
dense weight into stationary plans (``init_cnn`` then
``plan_cnn_weights``) in one jitted call; only the plans and the biases
are kept. The timed entry is ``jit(cnn_forward)`` over those plans, on a
pool of image batches made on the device from the seed.

The window is a closed loop with one batch in flight: submit a batch,
wait until its logits are ready, submit the next. It closes when the
first batch that is ready past ``seconds`` is, so every batch it ran is
counted, over the window's whole length (a count of whole batches over a
fixed length would move in steps of one batch).
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional

import numpy as np

from harness import counts, seeds, traffic


def layer_specs(cfg: Dict):
    """The configuration as the program's layer specs, named as its
    executor expects (``stem``, ``s<i>b<j>c1``/``c2``/``ds``, ``fc``)."""
    from repro.core.workloads import ConvSpec, DenseSpec
    out = []
    for l in counts.resnet_layers(cfg):
        if l["kind"] == "conv":
            out.append(ConvSpec(l["name"], l["hw"], l["hw"], l["cin"],
                                l["cout"], l["k"], l["k"], stride=l["stride"],
                                residual_add=l["name"].endswith("c2")))
        else:
            out.append(DenseSpec(l["name"], l["cin"], l["cout"]))
    return out


def pim_config(cfg: Dict):
    from repro.core.pim import PimConfig
    p = cfg["pim"]
    return PimConfig(weight_bits=p["weight_bits"], act_bits=p["act_bits"],
                     substrate=p["substrate"])


class Session:
    def __init__(self, cfg: Dict, mix: Dict, seed: int,
                 fault: Optional[str] = None):
        import jax
        import jax.numpy as jnp
        from repro.models.cnn import cnn_forward, init_cnn, plan_cnn_weights
        self.cfg, self.mix, self.seed = cfg, mix, seed
        layers = layer_specs(cfg)
        pim = pim_config(cfg)
        shapes = {}

        @jax.jit
        def make(key):
            params = init_cnn(layers, key)
            return ({n: p["b"] for n, p in params.items()},
                    plan_cnn_weights(params, layers, pim))

        key = seeds.jax_key(seed, seeds.WEIGHTS)
        for name, p in jax.eval_shape(lambda k: init_cnn(layers, k),
                                      key).items():
            shapes[name] = p["w"].shape
        self.biases, self.plans = make(key)

        def forward(biases, plans, x):
            # the executor reads each layer's float weight only for its
            # shape once a plan exists; a zero stand-in is dead code
            params = {n: {"w": jnp.zeros(shapes[n]), "b": b}
                      for n, b in biases.items()}
            if fault == "half_batch":
                half = x.shape[0] // 2
                y = cnn_forward(params, layers, x[:half], pim=pim,
                                plans=plans)
                return jnp.concatenate([y, y], axis=0)
            y = cnn_forward(params, layers, x, pim=pim, plans=plans)
            if fault == "answer":
                y = y.at[:, 0].add(1.0)
            return y

        self.forward = jax.jit(forward)
        pool = traffic.images(mix, cfg["image_size"], seed)
        self.pool = [pool[i] for i in range(pool.shape[0])]
        del pool
        self.counters: Dict[str, int] = {}
        jax.block_until_ready(self.forward(self.biases, self.plans,
                                           self.pool[0]))

    def window(self, seconds: float) -> Dict:
        import jax
        from repro.analysis.sanitize import CompileCounter
        n_pool = len(self.pool)
        last: Dict[int, object] = {}
        batches = 0
        with CompileCounter() as compiles, \
                jax.profiler.TraceAnnotation("window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            while True:
                i = batches % n_pool
                with jax.profiler.TraceAnnotation("forward"):
                    out = self.forward(self.biases, self.plans, self.pool[i])
                    out.block_until_ready()
                last[i] = out
                batches += 1
                if time.perf_counter() > deadline:
                    break
            t_close = time.perf_counter()
        b = int(self.mix["batch"])
        self.counters.update(batches=batches, images=batches * b)
        return {
            "end_to_end": {"images_per_s": batches * b / (t_close - t0)},
            "attempted": batches * b, "failed": 0,
            "window_s": t_close - t0,
            "compiles": sum(compiles.counts.values()),
            "counters": dict(self.counters),
            "samples": self._sample(last),
        }

    def _sample(self, last: Dict) -> List[Dict]:
        """Pool slots drawn from the seed, with the logits the window last
        produced for each."""
        k = int(self.mix["check"]["batches"])
        order = [int(i) for i in seeds.rng(self.seed, 7).permutation(
            len(self.pool)) if int(i) in last][:k]
        return [{"slot": i, "logits": np.asarray(last[i])} for i in order]

    def kernel_calls(self) -> List[counts.Call]:
        """Logical ``pim_matmul`` calls of every batch of the window."""
        return counts.cnn_calls(self.cfg, int(self.mix["batch"]),
                                self.counters.get("batches", 0))

    def true_int_ops(self) -> int:
        return counts.cnn_int_ops_per_image(self.cfg) * \
            self.counters.get("images", 0)

    def free(self) -> None:
        self.biases = self.plans = self.pool = self.forward = None
        gc.collect()


def setup(cfg: Dict, mix: Dict, seed: int, fault: Optional[str] = None
          ) -> Session:
    return Session(cfg, mix, seed, fault)


PROGRAMS = {"forward": r"^jit_forward\b"}
HOST_SPANS = ("forward",)
KERNELS = {"pim_matmul": r"pim_matmul"}
