"""The inference engine: AOT bucket compilation, placement, hot-swap.

Design (ISSUE 7 tentpole):

* **AOT bucket ladder** — the forward pass is lowered + compiled at
  construction for every batch-size bucket in the ladder
  (``DPTPU_SERVE_BUCKETS``), so no request ever hits a compile stall:
  the first request is as fast as the thousandth. Weights are a call
  ARGUMENT, not a captured constant, so a hot-swap never recompiles.

* **Bucket-invariant numerics** — a row's logits should not depend on
  which bucket served it. What holds on the installed toolchain
  (jax 0.9.0):

  - XLA's M=1 matmul lowers to a gemv whose reduction order differs
    from the M>=2 gemm path — countered by the **execution floor**:
    every bucket executes at ``max(bucket, 2)`` rows, so the
    single-request path rides the SAME gemm lowering as every padded
    bucket, at the cost of one duplicate row at bucket 1.
  - XLA:CPU's intra-op thread pool splits conv/gemm reductions by
    shape AND core count, and no per-executable option turns that off
    (``xla_cpu_multi_thread_eigen`` is still accepted but changes
    nothing). On a one-core host buckets agree bit for bit; on the
    8-core sandbox resnet18@32 rows differ by 7.7e-7 between exec
    sizes. The CPU contract is therefore ``BUCKET_PARITY_ATOL`` (fp32
    rounding), not 0; same-exec-size results (pad content, repeated
    calls) stay bit-identical. On the TPU the claim is checked by
    ``chip_smoke.py``, not assumed.

* **Padded-batch execution** — a bucket runs with ``n_valid`` real rows
  and ``exec - n_valid`` pad rows (row-0 repeats, the loader's padding
  convention); eval-mode forwards are row-independent (BN uses running
  stats), so pad content cannot perturb real rows, and the result is
  sliced to ``n_valid``.

* **Placement per family** (``resolve_placement``) — ``replicated``
  runs the single-program forward; ``tp`` opens a ``model``-axis mesh
  and shards params by the family's Megatron rule
  (dptpu/parallel/gspmd.py ``tp_specs_for_arch``; activations
  replicated, the partitioner inserts the per-block all-reduces).
  ``auto`` picks TP for the three families with a real rule when more
  than one device is visible, replicated otherwise.

* **Generation-tagged weights** — ``swap_weights`` installs a new
  weight generation without dropping in-flight requests: a dispatched
  batch pins the generation it was assigned (``acquire_generation``),
  every batch is served by exactly ONE generation (mixed-generation
  serving is structurally impossible — one pytree per call), and a
  superseded generation's buffers are dropped the moment its last
  in-flight batch releases (``old generation drains``).

* **Precision axis** (ISSUE 18) — the bucket ladder is compiled per
  PRECISION: ``_compiled[(precision, nexec)]``. ``fp32`` is the base
  ladder (today's path); ``bf16`` stores matmul weights bf16; ``int8``
  stores them int8 with per-channel scales (dptpu/ops/quant.py) and
  dequantizes in-graph to bf16 — the compiled HLO carries ``s8``
  params and ``bf16`` dots (statically asserted by the serve-quant
  budget config in ``dptpu check``). Each weight GENERATION carries
  its precision, so a quantized rollout is just a staged generation:
  it rides the canary machinery (shadow eval, top-1 agreement +
  max|Δlogit| gate, auto-rollback) and is NEVER silently promoted —
  ``stage_quantized`` also refuses to run without a verified
  calibration artifact (CRC + arch + weights-fingerprint match).

* **Per-shard TP loading** — under ``tp`` placement, weights are
  constructed shard-by-shard from the unified partition-rules
  projection (``jax.make_array_from_callback``: each device's shard is
  sliced from the host array on demand) instead of gathering the full
  array onto every device and resharding — the serve twin of the
  rules-table unification, locked at max|Δlogit| = 0 against the
  gathered path.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from dptpu import obs
from dptpu.serve.knobs import parse_buckets
from dptpu.utils.sync import OrderedLock

# the measured gemv/gemm divergence floor (module docstring): every
# executable's leading dim is >= 2 so all buckets share one lowering
EXEC_FLOOR = 2


# max|dlogit| allowed between two buckets serving the same row (module
# docstring): fp32 rounding from shape-dependent reduction splits
BUCKET_PARITY_ATOL = 1e-5


def resolve_placement(arch: str, placement: str,
                      device_count: Optional[int] = None) -> str:
    """``auto``/``replicated``/``tp`` -> the concrete placement, failing
    fast on impossible requests (explicit ``tp`` for a family with no TP
    rule, or on a single device) instead of silently degrading."""
    from dptpu.parallel.gspmd import tp_rule_for_arch

    if device_count is None:
        device_count = jax.device_count()
    rule = tp_rule_for_arch(arch)
    if placement == "tp":
        if rule == "dp_specs":
            raise ValueError(
                f"--placement=tp: no tensor-parallel sharding rule for "
                f"{arch!r} (TP families: vit_*, swin*, convnext_* — see "
                f"dptpu/parallel/gspmd.py tp_rule_for_arch); use "
                f"--placement=replicated"
            )
        if device_count < 2:
            raise ValueError(
                f"--placement=tp needs >= 2 devices to open a model "
                f"axis, found {device_count}"
            )
        return "tp"
    if placement == "replicated":
        return "replicated"
    # auto: TP where a family rule exists and there is a mesh to use it
    return "tp" if (rule != "dp_specs" and device_count >= 2) \
        else "replicated"


class ServeEngine:
    """AOT bucket-compiled, hot-swappable eval forward for one registry
    arch. ``variables`` takes explicit weights (tests/benches);
    ``pretrained=True`` loads the converted-torchvision ``<arch>.npz``
    (``DPTPU_PRETRAINED_DIR``); neither = random init (load-testing)."""

    def __init__(self, arch: str, *, buckets: Sequence[int] = (1, 4, 16, 64),
                 placement: str = "auto", num_classes: int = 1000,
                 image_size: int = 224, variables: Optional[dict] = None,
                 pretrained: bool = False,
                 compute_dtype=jnp.float32, verbose: bool = False):
        from dptpu.models import create_model

        self.arch = arch
        # immutable tuple, republished whole by add_bucket (one
        # GIL-atomic store, every named exec size compiled first) from
        # the single thread that ticks the serve-ladder actuator; all
        # other readers take lock-free snapshots
        self.buckets = parse_buckets(buckets, source="buckets")  # owned-by: tick-thread
        self.num_classes = num_classes
        self.image_size = image_size
        self.compute_dtype = compute_dtype
        self.model = create_model(
            arch, pretrained=pretrained, num_classes=num_classes
        )
        # built lazily at first sub-fp32 stage; duplicate off-lock
        # builds produce identical clones, so last-write-wins is benign
        self._bf16_model_cache = None  # dptpu: allow-guarded-by(idempotent lazy clone; racing stagers rebuild an identical module)
        self.placement = resolve_placement(arch, placement)
        input_shape = (1, image_size, image_size, 3)
        if variables is None:
            if pretrained:
                from dptpu.models.pretrained import load_pretrained_variables

                variables = load_pretrained_variables(
                    arch, self.model, input_shape=input_shape
                )
            else:
                init = self.model.init(
                    jax.random.PRNGKey(0),
                    np.zeros(input_shape, np.float32), train=False,
                )
                variables = {"params": init["params"],
                             "batch_stats": init.get("batch_stats", {})}
        variables = {"params": variables["params"],
                     "batch_stats": variables.get("batch_stats", {})}
        # host-side fp32 copy: the quantization source (stage_quantized
        # fingerprints + quantizes THESE exact weights) — one host copy,
        # never on device
        self._host_variables = jax.tree_util.tree_map(
            np.asarray, variables
        )

        self._mesh = None
        self._var_shardings = None
        self.tp_rule = "dp_specs"
        if self.placement == "tp":
            from jax.sharding import NamedSharding, PartitionSpec as P

            from dptpu.parallel.gspmd import tp_specs_for_arch
            from dptpu.parallel.mesh import MODEL_AXIS, make_mesh

            self._mesh = make_mesh(
                mesh_shape={MODEL_AXIS: jax.device_count()}
            )
            self.tp_rule, specs = tp_specs_for_arch(
                arch, variables["params"]
            )
            rep = NamedSharding(self._mesh, P())
            self._var_shardings = {
                "params": jax.tree_util.tree_map(
                    lambda s: NamedSharding(self._mesh, s), specs
                ),
                "batch_stats": jax.tree_util.tree_map(
                    lambda _: rep, variables["batch_stats"]
                ),
            }
            self._img_sharding = rep
            self._out_sharding = rep

        # generation store: {gen: device-placed variables}; a dispatched
        # batch pins its generation until its logits materialize.
        # _gen is the CURRENT (default-served) generation; _latest is the
        # id counter — they diverge while a canary generation is staged
        # (resident + pinned by its controller, but not current)
        self._lock = OrderedLock("serve.engine")
        self._gen = 1  # guarded-by: _lock
        self._latest = 1  # guarded-by: _lock
        self._weights: Dict[int, dict] = {1: self._place(variables)}  # guarded-by: _lock
        self._inflight: Dict[int, int] = {1: 0}  # guarded-by: _lock
        self._precision: Dict[int, str] = {1: "fp32"}  # guarded-by: _lock
        self._verbose = verbose

        # AOT compile the base ladder (dedup buckets that share an exec
        # size: 1 and 2 both execute at the floor); further precision
        # ladders compile lazily at first stage of that precision
        self._compiled = {}  # {(precision, nexec): executable}  # dptpu: allow-guarded-by(idempotent compile cache mutated off-lock by design; concurrent stagers race to identical executables and dict stores are atomic)
        self._compile_ladder("fp32", self._weights[1])

    # -- compilation ----------------------------------------------------

    def _forward(self, variables, images):
        from dptpu.train.step import normalize_images

        x = normalize_images(images, self.compute_dtype)
        out = self.model.apply(variables, x, train=False)
        return out.astype(jnp.float32)

    def _forward_int8(self, qvariables, images):
        from dptpu.ops.quant import dequantize_tree
        from dptpu.train.step import normalize_images

        # in-graph dequantize: weights STAY int8 in device memory (the
        # residency win); the convert+scale fuses into the consumer and
        # every dot runs bf16
        variables = {
            "params": dequantize_tree(qvariables["params"], jnp.bfloat16),
            "batch_stats": qvariables["batch_stats"],
        }
        x = normalize_images(images, jnp.bfloat16)
        out = self._bf16_model().apply(variables, x, train=False)
        return out.astype(jnp.float32)

    def _forward_bf16(self, variables, images):
        from dptpu.train.step import normalize_images

        x = normalize_images(images, jnp.bfloat16)
        out = self._bf16_model().apply(variables, x, train=False)
        return out.astype(jnp.float32)

    def _bf16_model(self):
        """The model at compute dtype bf16 — the sub-fp32 forwards MUST
        apply this twin, not ``self.model``: every registry module casts
        activations to its own ``dtype`` attribute (fp32 here), so
        applying the fp32 module would silently promote every dot back
        to f32 and keep only the residency win. The serve-quant HLO
        budget gate (`dptpu check`) asserts the requested dot dtypes
        statically, so that regression fails before any bench."""
        if self._bf16_model_cache is None:
            self._bf16_model_cache = self.model.clone(dtype=jnp.bfloat16)
        return self._bf16_model_cache

    def _forward_for(self, precision: str):
        return {"fp32": self._forward, "bf16": self._forward_bf16,
                "int8": self._forward_int8}[precision]

    def _compile_ladder(self, precision: str, placed_variables) -> None:
        """AOT-compile every bucket of the ladder at ``precision`` from
        a placed variables tree (idempotent; races between concurrent
        stagers install identical executables)."""
        var_structs = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            placed_variables,
        )
        for b in self.buckets:
            nexec = self.exec_batch(b)
            if (precision, nexec) in self._compiled:
                continue
            exe = self._compile_at(nexec, var_structs, precision)
            self._compiled[(precision, nexec)] = exe
            if self._verbose:
                print(f"=> serve: AOT-compiled {self.arch} bucket {b} "
                      f"(exec batch {nexec}, {self.placement}, "
                      f"{precision})")

    def _compile_at(self, nexec: int, var_structs, precision: str = "fp32"):
        img = jax.ShapeDtypeStruct(
            (nexec, self.image_size, self.image_size, 3), jnp.uint8
        )
        forward = self._forward_for(precision)
        if self.placement == "tp":
            fn = jax.jit(
                forward,
                in_shardings=(self._var_shardings, self._img_sharding),
                out_shardings=self._out_sharding,
            )
        else:
            fn = jax.jit(forward)
        return fn.lower(var_structs, img).compile()

    def exec_batch(self, bucket: int) -> int:
        """The executable's leading dim for ``bucket`` (the >= 2 floor)."""
        return max(int(bucket), EXEC_FLOOR)

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= ``n`` (the batcher's coalescing target)."""
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(
            f"{n} requests exceed the largest bucket "
            f"{self.buckets[-1]} — the batcher must split first"
        )

    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def add_bucket(self, bucket: int) -> Optional[int]:
        """Insert an INTERIOR bucket into the ladder at runtime (the
        tune controller's serve-ladder actuator, ISSUE 19): AOT-compile
        the new exec size for every resident precision FIRST, then
        publish the new ladder — no request ever hits a compile stall,
        and admission (``max_bucket``) never moves. Returns the bucket,
        or None when it already exists or falls outside
        ``(0, max_bucket)`` — the actuator reads None as "no headroom"
        and disarms cleanly."""
        bucket = int(bucket)
        if bucket < 1 or bucket >= self.max_bucket \
                or bucket in self.buckets:
            return None
        nexec = self.exec_batch(bucket)
        with self._lock:
            by_precision = {
                self._precision[g]: self._weights[g]
                for g in sorted(self._weights)
            }
        for precision, placed in by_precision.items():
            if (precision, nexec) in self._compiled:
                continue
            var_structs = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                placed,
            )
            exe = self._compile_at(nexec, var_structs, precision)
            self._compiled[(precision, nexec)] = exe
        # one GIL-atomic tuple store publishes the grown ladder to the
        # dispatch thread's bucket_for/max_bucket reads; every exec size
        # it names is compiled above, before the store
        self.buckets = tuple(sorted(self.buckets + (bucket,)))
        if self._verbose:
            print(f"=> serve: ladder grew to {self.buckets} "
                  f"(tune controller inserted bucket {bucket})")
        return bucket

    # -- weight generations ---------------------------------------------

    def _place(self, variables):
        if self.placement == "tp":
            # per-shard construction from the rules projection: each
            # device's addressable shard is SLICED from the host array
            # by the callback — the full array is never gathered onto
            # any device and then resharded (the old device_put path).
            # Bit-identical to the gathered path (same host values,
            # same final layout) — locked at max|Δlogit| = 0 by
            # tests/test_serve.py.
            def put(x, s):
                a = np.asarray(x)
                return jax.make_array_from_callback(
                    a.shape, s, lambda idx, _a=a: _a[idx]
                )

            return jax.tree_util.tree_map(
                put, variables, self._var_shardings,
            )
        return jax.device_put(variables)

    def _place_gathered(self, variables):
        """The pre-rules-projection placement (gather the full array to
        every device, let the sharding reshard) — kept ONLY as the = 0
        parity reference for the per-shard path."""
        if self.placement == "tp":
            return jax.tree_util.tree_map(
                lambda x, s: jax.device_put(np.asarray(x), s),
                variables, self._var_shardings,
            )
        return jax.device_put(variables)

    def swap_weights(self, variables) -> int:
        """Install a new weight generation (same tree/shapes — validated
        against the compiled signature by construction: a mismatched
        tree fails the compiled call loudly, not silently). In-flight
        batches keep serving their pinned generation; the old one is
        dropped when its last batch releases. Returns the new id."""
        variables = {"params": variables["params"],
                     "batch_stats": variables.get("batch_stats", {})}
        placed = self._place(variables)  # off-lock: device transfer
        with self._lock:
            self._latest += 1
            self._gen = self._latest
            self._weights[self._gen] = placed
            self._inflight[self._gen] = 0
            self._precision[self._gen] = "fp32"
            self._drop_drained_locked()
            return self._gen

    def stage_weights(self, variables, precision: str = "fp32") -> int:
        """Install a new generation WITHOUT making it current (the
        canary rollout's first half): the generation is resident and
        pinnable via ``acquire_generation(gen=...)``, but default
        traffic keeps serving the current one. The staged generation
        starts with ONE in-flight pin — the stager's — so draining
        cannot drop it before ``promote`` or ``discard_staged`` decides
        its fate. ``precision`` != fp32 expects an ALREADY-converted
        tree (``stage_quantized`` is the artifact-verified front door)
        and lazily compiles that precision's ladder. Returns the staged
        id."""
        variables = {"params": variables["params"],
                     "batch_stats": variables.get("batch_stats", {})}
        if precision != "fp32" and self.placement == "tp":
            raise ValueError(
                f"precision {precision!r} is not supported under tp "
                f"placement (quantized marker leaves have no sharding "
                f"rule projection yet) — serve quantized models "
                f"replicated"
            )
        placed = self._place(variables)  # off-lock: device transfer
        self._compile_ladder(precision, placed)  # off-lock: idempotent
        with self._lock:
            self._latest += 1
            gen = self._latest
            self._weights[gen] = placed
            self._inflight[gen] = 1  # the stager's pin
            self._precision[gen] = precision
            return gen

    def stage_quantized(self, calibration: str, precision: str = "int8"):
        """The quantized rollout's front door: verify the calibration
        artifact against THIS engine's arch and live weights (CRC +
        arch + weights fingerprint — dptpu/serve/quant.py names the
        recalibration command on any mismatch), quantize the host-side
        fp32 weights with the artifact's scales, and stage the result
        as a new generation. Returns ``(gen, meta)`` — ``meta`` carries
        the gate bounds the canary controller must enforce
        (``meta["bounds"]``: min top-1 agreement, max|Δlogit|). bf16
        precision needs no scales; the artifact is still required so
        every sub-fp32 deployment has a provenance record."""
        from dptpu.serve.quant import load_calibration, quantize_variables

        payload = load_calibration(
            calibration, arch=self.arch,
            params=self._host_variables["params"],
        )
        qvars = quantize_variables(
            self._host_variables, precision,
            scales=payload.get("scales") if precision == "int8" else None,
        )
        gen = self.stage_weights(qvars, precision=precision)
        return gen, payload["meta"]

    def promote(self, gen: int) -> None:
        """Make a staged generation CURRENT (the canary rollout's happy
        ending) and release the stager's pin; the superseded generation
        drains away exactly like a ``swap_weights`` predecessor."""
        with self._lock:
            if gen not in self._weights:
                raise KeyError(f"generation {gen} is not resident")
            if gen == self._gen:
                return
            self._gen = gen
            self._inflight[gen] -= 1
            self._drop_drained_locked()

    def discard_staged(self, gen: int) -> None:
        """Release the stager's pin WITHOUT promoting (canary rollback):
        the staged generation's buffers drop the moment its last
        in-flight canary batch releases."""
        with self._lock:
            if gen not in self._weights or gen == self._gen:
                return  # already dropped, or promoted out from under us
            self._inflight[gen] -= 1
            self._drop_drained_locked()

    def acquire_generation(self, gen: Optional[int] = None) -> int:
        """Pin a generation for one batch (default: the CURRENT one;
        a canary controller pins its staged id explicitly); the batch is
        served with this generation's weights no matter what swaps land
        while it is in flight."""
        with self._lock:
            if gen is None:
                gen = self._gen
            elif gen not in self._weights:
                raise KeyError(
                    f"generation {gen} is not resident (live: "
                    f"{sorted(self._weights)})"
                )
            self._inflight[gen] += 1
            return gen

    def release_generation(self, gen: int) -> None:
        with self._lock:
            self._inflight[gen] -= 1
            self._drop_drained_locked()

    def _drop_drained_locked(self):
        for g in [g for g in self._weights
                  if g != self._gen and self._inflight[g] == 0]:
            del self._weights[g]
            del self._inflight[g]
            del self._precision[g]

    def generations(self) -> Tuple[int, ...]:
        """Live (resident) generation ids — newest is current; older
        ones are draining."""
        with self._lock:
            return tuple(sorted(self._weights))

    @property
    def current_generation(self) -> int:
        with self._lock:
            return self._gen

    def generation_precision(self, gen: Optional[int] = None) -> str:
        """The precision axis of a resident generation (default: the
        current one)."""
        with self._lock:
            return self._precision[self._gen if gen is None else gen]

    def resident_bytes(self) -> Dict[int, int]:
        """Per-generation resident weight bytes — the HBM-residency
        meter SERVEBENCH's quantized arm reports (int8 matmul weights
        are 4x smaller than their fp32 generation)."""
        from dptpu.ops.quant import tree_nbytes

        with self._lock:
            return {g: tree_nbytes(w) for g, w in self._weights.items()}

    # -- execution ------------------------------------------------------

    def run_bucket(self, bucket: int, images_exec: np.ndarray,
                   n_valid: int, gen: Optional[int] = None) -> np.ndarray:
        """Run one padded bucket: ``images_exec`` is the FULL
        ``exec_batch(bucket)``-row array (pad rows already filled — the
        batcher repeats row 0), ``n_valid`` of which are real. Blocks
        until the logits are on the host (which is also the moment the
        input buffer is provably no longer read — the staging lease may
        release after this returns, CPU-PJRT aliasing included). Returns
        float32 ``[n_valid, num_classes]``."""
        nexec = self.exec_batch(bucket)
        if images_exec.shape[0] != nexec:
            raise ValueError(
                f"bucket {bucket} executes at {nexec} rows, got "
                f"{images_exec.shape[0]}"
            )
        owns_gen = gen is None
        if owns_gen:
            gen = self.acquire_generation()
        try:
            with self._lock:
                weights = self._weights[gen]
                precision = self._precision[gen]
            with obs.get_tracer().span("serve_device"):
                out = self._compiled[(precision, nexec)](
                    weights, images_exec
                )
                logits = np.asarray(out)  # blocks: device done with input
        finally:
            if owns_gen:
                self.release_generation(gen)
        return logits[:n_valid]

    def infer(self, images: np.ndarray) -> np.ndarray:
        """Convenience single-shot path (tests, the CLI self-test): pick
        the bucket for ``len(images)``, pad with row-0 repeats, run,
        slice. The batcher's zero-copy path calls ``run_bucket`` on a
        staging-slot view instead."""
        images = np.ascontiguousarray(images, dtype=np.uint8)
        n = images.shape[0]
        nexec = self.exec_batch(self.bucket_for(n))
        if n < nexec:
            pad = np.broadcast_to(
                images[0], (nexec - n,) + images.shape[1:]
            )
            images = np.concatenate([images, pad], axis=0)
        return self.run_bucket(self.bucket_for(n), images, n)
