"""The front-door session object: ``api.compile(...) -> CompiledModel``.

One object is the compiled network and its serving entry:

    model = api.compile(graph, HurryConfig(array_rows=511))
    probs = model.run(x)                    # jitted; cached per batch bucket
    report = model.simulate()               # cycles/energy/area SimReport
    model.save("model.npz"); m2 = api.load("model.npz")   # skip compile

``api.compile`` **packs the weights at compile time**
(``program/pack.py`` — pre-quantized int8 mount planes, the numeric
analogue of programming conductances), so ``run`` only ever quantizes
the input and dispatches kernels; no weight touches float math after
compile.  ``run`` keeps one jitted executor per output flavor and pads
incoming batches up to a small bucket ladder (edge replication —
slice-exact, see ``pad_batch``), so varying-traffic batch sizes
share one XLA executable per bucket instead of compiling per exact
shape.  ``simulate`` runs the analytical chip model on the *same*
graph the numeric program was compiled from — one network definition,
both evaluations.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.baselines import SimReport, simulate_isaac, simulate_misca
from repro.core.simulator import simulate_hurry
from repro.program.compile import CrossbarProgram, compile_network
from repro.program.execute import execute_packed
from repro.program.pack import PackedProgram, pack_program

from .config import HurryConfig
from .graph import NetworkBuilder, NetworkGraph
from .serialize import load_model, save_model
from .zoo import GRAPHS

SIM_ARCHS = ("hurry", "isaac-128", "isaac-256", "isaac-512", "misca")

# default batch-bucket ladder: powers of two cover varying traffic with
# at most 2x padding and ~10 executables total
BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def bucket_batch(b: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= b, or b itself beyond the ladder (exact shape).

    Order-insensitive, so a user-supplied unsorted ladder never pads
    more than the tightest eligible bucket.
    """
    return min((s for s in buckets if s >= b), default=b)


def pad_batch(x: jnp.ndarray, bucket: int) -> jnp.ndarray:
    """Pad the batch axis up to ``bucket`` by edge replication.

    Replicating the last request (rather than zero-filling) keeps every
    per-tensor quantization statistic exact: ``max(|x|)`` over
    duplicated rows equals the unpadded max at every stage, so the kept
    rows of a bucketed run are bit-identical to the unbucketed run.
    """
    b = x.shape[0]
    if bucket == b:
        return x
    return jnp.pad(x, ((0, bucket - b),) + ((0, 0),) * (x.ndim - 1),
                   mode="edge")


@dataclasses.dataclass
class CompiledModel:
    """A compiled+packed network: runnable, simulatable, persistable."""

    graph: NetworkGraph
    config: HurryConfig
    program: CrossbarProgram
    params: dict
    packed: PackedProgram
    buckets: tuple[int, ...] = BUCKETS
    _fns: dict = dataclasses.field(default_factory=dict, repr=False,
                                   compare=False)
    # (logits, bucket) pairs ``run`` has called, and requests served:
    # what the ``repro.run`` trace spans report
    _called: set = dataclasses.field(default_factory=set, repr=False,
                                     compare=False)
    _requests: int = dataclasses.field(default=0, repr=False, compare=False)

    # -- numeric execution -------------------------------------------------

    def _fn(self, logits: bool):
        """The jitted executor of one output flavor, built once."""
        fn = self._fns.get(logits)
        if fn is None:
            cfg = self.config
            fn = jax.jit(lambda pk, v: execute_packed(
                pk, v, block_m=cfg.block_m, block_n=cfg.block_n,
                return_logits=logits))
            self._fns[logits] = fn
        return fn

    def run(self, x: jnp.ndarray, *, logits: bool = False) -> jnp.ndarray:
        """Execute the packed program on a batch.

        Returns the program's output buffer (softmax probabilities when
        the graph ends in softmax); ``logits=True`` returns the last
        GEMM output.  The jitted executor is built once per flavor;
        batches pad up to the model's bucket ladder (slice-exact edge
        replication) and XLA caches one executable per bucket — varying
        traffic shapes stay pure execution on ~10 executables.

        Under a ``jax.profiler`` trace each call writes a host span
        ``repro.run`` with three children: ``repro.run.pad`` (padding
        to the bucket, which copies a host array to the device; an
        unpadded one is copied in the call), ``repro.run.call`` (the
        executor's dispatch) and ``repro.run.slice`` (the ``[:b]``).
        All four carry ``request``, one id per call of this model;
        ``repro.run`` also ``batch`` and ``bucket``; ``repro.run.call``
        ``bucket`` and ``new`` (1 on the first call of a flavor and
        bucket: the one that compiles or loads from the compile cache).
        Without a trace they cost a few microseconds a call.
        """
        fn = self._fn(logits)
        b = x.shape[0]
        bucket = bucket_batch(b, self.buckets)
        self._requests += 1
        req = self._requests
        with TraceAnnotation("repro.run", request=req, batch=b,
                             bucket=bucket):
            with TraceAnnotation("repro.run.pad", request=req):
                x = pad_batch(x, bucket)
            new = (logits, bucket) not in self._called
            with TraceAnnotation("repro.run.call", request=req,
                                 bucket=bucket, new=int(new)):
                y = fn(self.packed, x)
            self._called.add((logits, bucket))
            with TraceAnnotation("repro.run.slice", request=req):
                return y[:b]

    def compiled_text(self, x, *, logits: bool = False) -> str:
        """The optimized HLO text of the executable ``run`` calls for a
        request shaped like ``x`` (an array or ``jax.ShapeDtypeStruct``):
        its bucket's program, each instruction's ``op_name`` carrying the
        stage and phase scopes of ``program/execute.py``.  Read-only;
        after ``run`` has served that bucket the compile is a load from
        JAX's compile cache, where one is on."""
        shape = (bucket_batch(x.shape[0], self.buckets),) + x.shape[1:]
        v = jax.ShapeDtypeStruct(shape, x.dtype)
        return self._fn(logits).lower(self.packed, v).compile().as_text()

    def warmup(self, batch: int = 1, *, logits: bool = False,
               seq_len: int = 16) -> None:
        """Pay trace + compile for one batch bucket ahead of traffic.

        ``seq_len`` sizes the dummy token axis of sequence-input
        programs (image/fc-input programs ignore it).
        """
        x = jnp.zeros(self.program.input_shape(batch, seq_len=seq_len),
                      jnp.float32)
        jax.block_until_ready(self.run(x, logits=logits))

    # -- analytical evaluation --------------------------------------------

    def simulate(self, arch: str = "hurry") -> SimReport:
        """Cycle/energy/area report for this graph on ``arch``.

        ``arch`` is one of ``SIM_ARCHS`` — the HURRY chip this model was
        compiled for, or an ISAAC/MISCA comparison chip sharing its
        geometry.
        """
        if arch not in SIM_ARCHS:
            raise ValueError(f"unknown arch {arch!r}; one of {SIM_ARCHS}")
        from repro.core.workload import SEQ_KINDS
        if any(l.kind in SEQ_KINDS for l in self.graph.layers):
            raise ValueError(
                f"{self.graph.name}: the analytical chip model does not "
                "cover sequence workloads yet (dynamic-operand mounts "
                "have no Algorithm 1/2 placement); numeric execution "
                "via .run() is fully supported")
        layers = list(self.graph.layers)
        if arch == "hurry":
            return simulate_hurry(layers, chip=self.config.chip(),
                                  name=f"hurry/{self.graph.name}")
        if arch == "misca":
            return simulate_misca(layers, chip=self.config)
        return simulate_isaac(layers, int(arch.split("-")[1]),
                              chip=self.config)

    # -- introspection / persistence --------------------------------------

    def summary(self) -> str:
        cfg = self.program.cfg
        lines = [f"CompiledModel({self.graph.name}): "
                 f"{len(self.graph.layers)} layers, input "
                 f"{self.program.input_shape(1)[1:]}, "
                 f"{cfg.rows}x{cfg.cols} arrays / {cfg.adc_bits}-bit ADC"
                 f"{' (clip-free)' if cfg.clip_free else ''}",
                 self.program.summary()]
        return "\n".join(lines)

    def save(self, path: str) -> str:
        """Persist program + params + packed planes: serving skips both
        compilation and weight re-quantization."""
        return save_model(self, path)


def compile(network, config: HurryConfig | None = None, *,
            params: dict | None = None, seed: int = 0,
            buckets: tuple[int, ...] | None = BUCKETS) -> CompiledModel:
    """Lower a network to a ``CompiledModel`` under one unified config.

    ``network`` is a ``NetworkGraph``, a ``NetworkBuilder`` (built
    implicitly), a registry name (``repro.api.zoo``), or a raw
    ``LayerSpec`` list.  ``params`` defaults to the graph-derived He
    init (``NetworkGraph.init_params``).  Weights are packed here —
    ``run`` never re-derives them.  ``buckets`` is the batch-size
    ladder ``run`` pads up to (None or ``()`` disables bucketing: one
    executable per exact batch shape).
    """
    config = config or HurryConfig()
    if isinstance(network, str):
        graph = GRAPHS[network]()
    elif isinstance(network, NetworkBuilder):
        graph = network.build()
    elif isinstance(network, NetworkGraph):
        graph = network
    else:
        graph = NetworkGraph.from_layers(network)
    program = compile_network(graph, config=config)
    if params is None:
        params = graph.init_params(jax.random.PRNGKey(seed))
    return CompiledModel(graph=graph, config=config, program=program,
                         params=params,
                         packed=pack_program(program, params),
                         buckets=tuple(buckets or ()))


def load(path: str) -> CompiledModel:
    """Load a ``CompiledModel`` from ``save`` — no compilation happens."""
    return load_model(path)
