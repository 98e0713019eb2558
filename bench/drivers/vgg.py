"""Drive VGG16 (stages of 3x3 convs, a max-pool after each, a dense head
over the flattened last map) through the program's PIM executor as a
client.

The client is ``drivers/cnn.py``'s: set-up builds the network from the
seed and programs every conv and dense weight into stationary plans
(``init_cnn`` then ``plan_cnn_weights``) in one jitted call, and the
timed entry is ``jit(cnn_forward)`` over those plans, on a pool of image
batches made on the device from the seed. Set-up, the planted faults,
the window (a closed loop with one batch in flight) and the sampled
batches are inherited from there. What is VGG's own is here: the layer
specs read from the configuration, and the logical kernel calls and
operations reckoned from the configuration alone.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from harness import cells, counts

# ``drivers/cnn.py``'s client builds its network from its own module's
# ``layer_specs``; this private copy of that module is given VGG's below
_cnn = cells.load_module(cells.BENCH / "drivers" / "cnn.py",
                         "driver_cnn_for_vgg")
pim_config = _cnn.pim_config


def layers(cfg: Dict) -> List[Dict]:
    """Every conv and dense layer, in the order the initializer draws
    them: per stage its convs ``s<i>c<j>`` (stride 1, SAME) on the
    stage's map, which a ``pool_size`` max-pool shrinks after the stage;
    then ``fc1`` over the last pooled map (``hw`` its side), the rest of
    ``fc``, and the classifier."""
    k, hw, c = cfg["kernel_size"], cfg["image_size"], 3
    out = []
    for s, (ch, convs) in enumerate(cfg["stages"]):
        for j in range(convs):
            out.append(dict(kind="conv", name=f"s{s}c{j}", hw=hw, cin=c,
                            cout=ch, k=k))
            c = ch
        hw //= cfg["pool_size"]
    cin = hw * hw * c
    for i, n in enumerate(list(cfg["fc"]) + [cfg["num_classes"]]):
        out.append(dict(kind="dense", name=f"fc{i + 1}",
                        hw=hw if i == 0 else 0, cin=cin, cout=n))
        cin = n
    return out


def layer_specs(cfg: Dict):
    """The configuration as the program's layer specs; ``fc1`` states the
    map it flattens."""
    from repro.core.workloads import ConvSpec, DenseSpec
    return [ConvSpec(l["name"], l["hw"], l["hw"], l["cin"], l["cout"],
                     l["k"], l["k"]) if l["kind"] == "conv" else
            DenseSpec(l["name"], l["cin"], l["cout"], in_h=l["hw"],
                      in_w=l["hw"])
            for l in layers(cfg)]


_cnn.layer_specs = layer_specs


def calls(cfg: Dict, batch: int, times: int) -> List[counts.Call]:
    """Logical kernel calls of ``times`` forward passes over ``batch``
    images: a conv's rows are its output pixels (stride 1: as many as its
    input's), its K the 3x3 patch; a dense layer's rows are the images."""
    return [(batch * l["hw"] ** 2, l["k"] ** 2 * l["cin"], l["cout"], times)
            if l["kind"] == "conv" else (batch, l["cin"], l["cout"], times)
            for l in layers(cfg)]


class Session(_cnn.Session):
    def kernel_calls(self) -> List[counts.Call]:
        """Logical ``pim_matmul`` calls of every batch of the window."""
        return calls(self.cfg, int(self.mix["batch"]),
                     self.counters.get("batches", 0))

    def true_int_ops(self) -> int:
        per_image = sum(counts.matmul_ops(m, k, n)
                        for m, k, n, _ in calls(self.cfg, 1, 1))
        return per_image * self.counters.get("images", 0)


def setup(cfg: Dict, mix: Dict, seed: int, fault: Optional[str] = None
          ) -> Session:
    return Session(cfg, mix, seed, fault)


PROGRAMS, HOST_SPANS, KERNELS = _cnn.PROGRAMS, _cnn.HOST_SPANS, _cnn.KERNELS
