"""``repro.api`` — the front door to the HURRY stack.

Author a network with ``NetworkBuilder`` (shape inference + build-time
validation), configure the chip/crossbar/executor with one
``HurryConfig``, then::

    model = api.compile(graph, config)   # scheduler -> CrossbarProgram
    probs = model.run(x)                 # Pallas crossbar + fused-FB
    report = model.simulate()            # cycles / energy / area
    model.save(path); api.load(path)     # serve without recompiling

The three paper CNNs and the ``vit_tiny``, ``deit_ti`` and ``swin_t``
transformers
live in ``repro.api.zoo`` as builder programs; ``api.compile(name)``
looks a name up there.  Sequence graphs (DESIGN.md §9)
compile to the same program stack: attention lowers into
dynamic-operand GEMM stages that mount runtime activations on the
crossbar per batch.
"""

from .config import HurryConfig
from .graph import NetworkBuilder, NetworkGraph
from .model import SIM_ARCHS, CompiledModel, compile, load
from .zoo import (GRAPHS, alexnet_graph, deit_graph, resnet18_graph,
                  swin_graph, vgg16_graph, vit_tiny, vit_tiny_graph)

__all__ = [
    "HurryConfig", "NetworkBuilder", "NetworkGraph",
    "CompiledModel", "compile", "load", "SIM_ARCHS",
    "GRAPHS", "alexnet_graph", "vgg16_graph", "resnet18_graph",
    "vit_tiny", "vit_tiny_graph", "deit_graph", "swin_graph",
]
