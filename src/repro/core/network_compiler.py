"""Multi-layer network compilation + chained execution (paper §4.2, Fig. 12).

``compile_network`` lowers a layer list into per-layer VTA programs sharing
one global DRAM allocation (the paper: "the data are allocated in the DRAM
and the instructions are adapted to match this allocation strategy" — here
the layers compile directly against the shared allocator, so no relocation
pass is needed and every instruction's logical addresses are final).

``NetworkProgram.run_functional`` then executes the chain on the functional
simulator with the paper's host-side reshaping between VTA executions:

  (i)  binary-decode the OUT region → blocks → matrix → remove padding,
       extract pooled rows → ``mat2tensor``;
  (ii) next layer's ``im2row`` (or NCHW flatten) → pad → split → binarise →
       written into the next program's INP region of the shared DRAM image.

Stage (ii) recomputes bytes that the compiler already placed in the image
(the compiler compiled every layer against reference activations); the run
asserts they agree — any divergence is a compilation bug, which is exactly
the traceability check the paper's workflow enables.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .conv_lowering import ConvGeometry, gather_rows, im2row_batch
from .cycle_model import CycleReport, analyze_programs
from .dram import DramAllocator
from .errors import CompileError
from .hwconfig import VTAConfig, vta_default
from .layer_compiler import (CompiledLayer, LayerSpec, compile_layer,
                             decode_layer_output, decode_layer_output_batch,
                             layer_matrices, row_gather)
from .layout import (batch_matrix_to_binary, matrix_to_binary,
                     should_pad_height)
from .simulator import (SimReport, decode_out_region, decode_out_region_batch,
                        make_simulator, run_instructions)
from repro.trace import span

# The real backend sets, enumerated once so refusal messages, the serving
# engine (repro.serving.vta) and the tests never drift out of sync again:
# ``serve`` runs a batch — on the batched interpreter or the pallas kernel;
# ``serve_one`` runs the per-image simulators.
SERVE_BACKENDS = ("batched", "pallas")
SERVE_ONE_BACKENDS = ("oracle", "fast")


@dataclasses.dataclass
class NetworkProgram:
    """Everything needed to run a compiled network on a VTA.

    ``input_sources``/``residual_sources`` generalise the chain to a DAG
    schedule (graph lowering, DESIGN.md §Graph): layer *k* reads its input
    from the semantic output of layer ``input_sources[k]`` (``-1`` = the
    network input) and — when ``residual_sources[k]`` is not None — stages
    that layer's output as its on-VTA residual operand.  ``None`` for both
    fields keeps the classic linear chain (layer k feeds layer k+1).
    """

    config: VTAConfig
    allocator: DramAllocator
    layers: List[CompiledLayer]
    input_tensor: np.ndarray
    input_sources: Optional[List[int]] = None
    residual_sources: Optional[List[Optional[int]]] = None

    def _sources(self) -> List[int]:
        if self.input_sources is not None:
            return self.input_sources
        return list(range(-1, len(self.layers) - 1))

    def _res_sources(self) -> List[Optional[int]]:
        if self.residual_sources is not None:
            return self.residual_sources
        return [None] * len(self.layers)

    # ------------------------------------------------------------------
    def gemm_loops(self) -> int:
        """§5.1 metric over the whole network (LeNet-5: 2942)."""
        return sum(l.program.gemm_loops() for l in self.layers)

    def gemm_loops_per_layer(self) -> List[int]:
        return [l.program.gemm_loops() for l in self.layers]

    def chunks_per_layer(self) -> List[int]:
        """SRAM chunks per layer (§3.3 "steps 2 to 5 must be repeated") —
        > 1 anywhere means the network genuinely exceeds a single SRAM
        residency and exercises the multi-chunk compiler (DESIGN.md §3)."""
        return [l.n_chunks for l in self.layers]

    def cycle_report(self) -> CycleReport:
        return analyze_programs([l.program for l in self.layers])

    def dram_image(self) -> np.ndarray:
        image = np.zeros(self.allocator.image_size(), dtype=np.uint8)
        for layer in self.layers:
            layer.program.place_segments(image)
        return image

    # ------------------------------------------------------------------
    def run_functional(self, *, check_chaining: bool = True,
                       backend: str = "oracle", fault_hook=None
                       ) -> Tuple[np.ndarray, List[SimReport]]:
        """Fig. 12: one VTA execution per layer + host reshaping between.

        Returns the final layer's semantic output (fc → (rows, F) int8
        matrix) and the per-execution simulator reports.  ``backend="fast"``
        runs each layer on the vectorised interpreter; per-layer instruction
        plans are compiled once and cached on the layer programs, so
        repeated runs (batch serving) pay only the array work.  ``backend``
        is a simulator's (:func:`~repro.core.simulator.make_simulator`,
        which refuses ``"pallas"``).
        """
        image = self.dram_image()
        reports: List[SimReport] = []
        sems: List[np.ndarray] = []
        srcs, rsrcs = self._sources(), self._res_sources()
        for k, layer in enumerate(self.layers):
            if k > 0:        # layer 0's INP was placed at compile time
                sem_in = (self.input_tensor if srcs[k] < 0
                          else sems[srcs[k]])
                A, _, _ = layer_matrices(layer.spec,
                                         np.asarray(sem_in, dtype=np.int8),
                                         row_align=self.config.block_size)
                if check_chaining:
                    np.testing.assert_array_equal(
                        A, layer.input_matrix,
                        err_msg=f"layer {srcs[k]}->{k} reshaping mismatch")
                inp_bin, _ = matrix_to_binary(
                    A, self.config.block_size, self.config.inp_dtype)
                region = layer.program.regions["inp"]
                start = region.phys_addr - self.allocator.offset
                image[start:start + len(inp_bin)] = np.frombuffer(
                    inp_bin, dtype=np.uint8)
            if rsrcs[k] is not None:
                sem_res = (self.input_tensor if rsrcs[k] < 0
                           else sems[rsrcs[k]])
                self._stage_residual(image, layer, sem_res,
                                     check=check_chaining)
            sim = make_simulator(self.config, image, backend=backend)
            reports.append(run_instructions(
                sim, layer.program.instructions, program=layer.program,
                fault_hook=self._layer_hook(fault_hook, k)))
            image = sim.dram   # VTA wrote its OUT region
            out_mat = decode_out_region(layer.program, image)
            sems.append(decode_layer_output(layer, out_mat))
        return sems[-1], reports

    def verify(self, *, backend: str = "oracle"
               ) -> Tuple[np.ndarray, List[SimReport]]:
        """Run the chain and check the final output against the compiler's
        per-layer reference.  Returns (final output, reports)."""
        out, reports = self.run_functional(backend=backend)
        expected = self.layers[-1].ref_output_matrix
        if self.layers[-1].spec.kind == "conv":
            from .conv_lowering import mat2tensor
            expected = mat2tensor(expected, self.layers[-1].out_h,
                                  self.layers[-1].out_w)
        np.testing.assert_array_equal(out, expected)
        return out, reports

    # ------------------------------------------------------- serving --
    @staticmethod
    def _layer_hook(fault_hook, k: int):
        """Adapt a network-level ``hook(sim, layer_idx, insn_idx)`` to the
        simulator-level ``hook(sim, insn_idx)`` for layer ``k`` — the
        injection/watchdog point of DESIGN.md §Hardening."""
        if fault_hook is None:
            return None
        return lambda sim, i: fault_hook(sim, k, i)

    def plans(self) -> List[object]:
        """Per-layer compiled instruction plans, cached on the layer
        programs — the compile-once/serve-many contract: the returned
        objects are identical across repeated :meth:`serve` calls."""
        from .fast_simulator import plan_for
        return [plan_for(layer.program) for layer in self.layers]

    def input_signature(self) -> Tuple[Tuple[int, ...], np.dtype]:
        """(shape, dtype) one request image must have — the admission
        contract the serving engine (DESIGN.md §Serving) validates at
        submit time instead of failing layers deep into staging."""
        return tuple(self.input_tensor.shape), np.dtype(np.int8)

    def plan_shapes(self) -> List[Dict[str, int]]:
        """Per-layer compiled geometry the serving layer batches against:
        INP/OUT (and residual) region sizes plus chunk counts.  Purely
        introspective — reading it never compiles or invalidates plans."""
        shapes: List[Dict[str, int]] = []
        for layer in self.layers:
            regions = layer.program.regions
            shapes.append({
                "name": layer.spec.name,
                "inp_nbytes": regions["inp"].nbytes,
                "out_nbytes": regions["out"].nbytes,
                "res_nbytes": (regions["res"].nbytes
                               if "res" in regions else 0),
                "n_chunks": layer.n_chunks,
            })
        return shapes

    def padded_batch_sizes(self, max_batch: int) -> Tuple[int, ...]:
        """The closed set of stack shapes the engine serves at: powers of
        two up to ``max_batch`` (plus ``max_batch`` itself when it is not
        a power of two).  Padding a formed batch up to the next rung
        keeps the compile-once contract — the batch engines see a small
        fixed family of ``(B, nbytes)`` stacks instead of one shape per
        occupancy."""
        if max_batch < 1:
            raise CompileError(
                f"padding ladder needs max_batch >= 1, got {max_batch} "
                f"(a degenerate ladder would defer the failure to "
                f"padded_size deep inside a worker)",
                constraint="ladder-max-batch")
        sizes = []
        b = 1
        while b < max_batch:
            sizes.append(b)
            b *= 2
        sizes.append(max_batch)
        return tuple(sizes)

    def _stage_layer_input(self, dram_row: np.ndarray, layer: CompiledLayer,
                           semantic_input: np.ndarray) -> None:
        """§4.2 stage (ii) for one request: im2row/flatten → pad → split →
        binarise → written into the layer's INP region of ``dram_row``
        (a view into the batch stack, so writes land in place)."""
        A, _, _ = layer_matrices(layer.spec,
                                 np.asarray(semantic_input, dtype=np.int8),
                                 row_align=self.config.block_size)
        inp_bin, _ = matrix_to_binary(A, self.config.block_size,
                                      self.config.inp_dtype)
        region = layer.program.regions["inp"]
        if len(inp_bin) != region.nbytes:
            raise ValueError(
                f"layer {layer.spec.name!r}: staged input is "
                f"{len(inp_bin)} bytes, INP region holds {region.nbytes} — "
                f"request shape does not match the compiled geometry")
        start = region.phys_addr - self.allocator.offset
        dram_row[start:start + len(inp_bin)] = np.frombuffer(inp_bin,
                                                             dtype=np.uint8)

    def _stage_layer_input_batch(self, stack: np.ndarray,
                                 layer: CompiledLayer,
                                 sems: List[np.ndarray]) -> None:
        """Batched §4.2 stage (ii): all requests share one lowering
        geometry, so im2row and the pad/split/binarise pipeline run once
        over the whole stack (``im2row_batch`` / ``batch_matrix_to_binary``)
        instead of once per request."""
        spec = layer.spec
        arrs = np.stack([np.asarray(s, dtype=np.int8) for s in sems])
        if spec.kind == "conv":
            _, c, kh, kw = spec.weights.shape
            A = im2row_batch(arrs[:, 0], kh, kw, spec.stride, spec.padding)
            geo = ConvGeometry(c, arrs.shape[-2], arrs.shape[-1], kh, kw,
                               spec.stride, spec.padding)
            gather = row_gather(spec, geo, self.config.block_size)
            if gather is not None:
                A = gather_rows(A, gather)
        else:
            A = arrs.reshape(len(sems), 1, -1)       # NCHW flatten / (1, D)
        raw = batch_matrix_to_binary(A, self.config.block_size,
                                     self.config.inp_dtype)
        region = layer.program.regions["inp"]
        if raw.shape[1] != region.nbytes:
            raise ValueError(
                f"layer {layer.spec.name!r}: staged input is "
                f"{raw.shape[1]} bytes, INP region holds {region.nbytes} — "
                f"request shape does not match the compiled geometry")
        start = region.phys_addr - self.allocator.offset
        stack[:, start:start + raw.shape[1]] = raw

    def _stage_residual(self, dram_row: np.ndarray, layer: CompiledLayer,
                        semantic: np.ndarray, *, check: bool = False) -> None:
        """Stage a residual layer's skip operand: semantic int8 activation
        → int32 (M, N) matrix → ACC-format binary in the layer's ``res``
        region (the second on-VTA ALU operand, DESIGN.md §Graph)."""
        from .layer_compiler import residual_operand_matrix
        R = residual_operand_matrix(layer.spec, semantic,
                                    layer.residual_matrix.shape)
        if check:
            np.testing.assert_array_equal(
                R, layer.residual_matrix,
                err_msg=f"layer {layer.spec.name!r}: residual operand "
                        f"mismatch")
        raw, _ = matrix_to_binary(R, self.config.block_size,
                                  self.config.acc_dtype)
        region = layer.program.regions["res"]
        if len(raw) != region.nbytes:
            raise ValueError(
                f"layer {layer.spec.name!r}: staged residual is "
                f"{len(raw)} bytes, RES region holds {region.nbytes}")
        start = region.phys_addr - self.allocator.offset
        dram_row[start:start + len(raw)] = np.frombuffer(raw, dtype=np.uint8)

    def _stage_residual_batch(self, stack: np.ndarray, layer: CompiledLayer,
                              sems: List[np.ndarray]) -> None:
        """Batched residual staging: one geometry, one pad/split/binarise
        pass over the whole request stack (as `_stage_layer_input_batch`,
        but into the ``res`` region with ACC-format int32 structures)."""
        from .layer_compiler import residual_operand_matrix
        Rs = np.stack([residual_operand_matrix(layer.spec, s,
                                               layer.residual_matrix.shape)
                       for s in sems])
        raw = batch_matrix_to_binary(Rs, self.config.block_size,
                                     self.config.acc_dtype)
        region = layer.program.regions["res"]
        if raw.shape[1] != region.nbytes:
            raise ValueError(
                f"layer {layer.spec.name!r}: staged residual is "
                f"{raw.shape[1]} bytes, RES region holds {region.nbytes}")
        start = region.phys_addr - self.allocator.offset
        stack[:, start:start + raw.shape[1]] = raw

    def _as_image_list(self, images) -> List[np.ndarray]:
        """Normalise a request batch: a sequence of per-image tensors
        (each shaped like ``input_tensor``), or one stacked array whose
        leading axis is the batch — ``(B, C, H, W)`` for a conv-first
        network with ``(1, C, H, W)`` inputs, ``(B, D)`` for fc-first."""
        if isinstance(images, np.ndarray):
            want = self.input_tensor.shape
            if images.shape[1:] == want:                 # (B,) + full shape
                return [img for img in images]
            if images.ndim == len(want) and images.shape[1:] == want[1:]:
                return [img[None] for img in images]     # batch axis leads
            raise ValueError(
                f"cannot interpret stacked input of shape {images.shape} "
                f"as a batch of {want} images")
        imgs = list(images)
        if not imgs:
            raise ValueError("empty request batch")
        return [np.asarray(img) for img in imgs]

    def serve_one(self, image: np.ndarray, *, backend: str = "fast",
                  fault_hook=None, count_overflows: bool = False,
                  guard=None):
        """One inference request: stage the image into layer 0's INP
        region, then run the chained per-layer VTA executions (Fig. 12)
        with the host reshaping between.  The per-layer instruction plans
        are cached on the programs, so requests after the first pay no
        plan compilation.

        ``backend`` is one of :data:`SERVE_ONE_BACKENDS` — ``"fast"``
        (default, the vectorised plan-compiling interpreter) or
        ``"oracle"`` (the per-struct reference interpreter); the batch
        engines, the pallas kernel among them, are :meth:`serve`'s, not
        this path's.  Both are bit-identical.

        ``guard`` (a :class:`repro.harden.GuardPolicy`) routes the request
        through the integrity-guarded path — CRC verification, instruction
        validation, bounded restore-and-retry — and changes the return
        value to ``(output, GuardReport)`` (DESIGN.md §Hardening).
        ``fault_hook(sim, layer_idx, insn_idx)`` fires before each
        instruction of each layer (the harden/ injection point)."""
        if backend not in SERVE_ONE_BACKENDS:
            raise CompileError(
                f"serve_one supports backend in {SERVE_ONE_BACKENDS}, got "
                f"{backend!r} (the batch engines run through "
                f'serve(backend="batched") and serve(backend="pallas"))',
                constraint="serve-one-backend")
        if guard is not None:
            from repro.harden import guards as _guards
            return _guards.guarded_serve_one(
                self, image, guard, backend=backend, fault_hook=fault_hook)
        image_mem = self.dram_image()
        self._stage_layer_input(image_mem, self.layers[0], image)
        sems: List[np.ndarray] = []
        srcs, rsrcs = self._sources(), self._res_sources()
        for k, layer in enumerate(self.layers):
            if k > 0:
                sem_in = image if srcs[k] < 0 else sems[srcs[k]]
                self._stage_layer_input(image_mem, layer, sem_in)
            if rsrcs[k] is not None:
                sem_res = image if rsrcs[k] < 0 else sems[rsrcs[k]]
                self._stage_residual(image_mem, layer, sem_res)
            sim = make_simulator(self.config, image_mem, backend=backend,
                                 count_overflows=count_overflows)
            run_instructions(sim, layer.program.instructions,
                             program=layer.program,
                             fault_hook=self._layer_hook(fault_hook, k))
            image_mem = sim.dram
            out_mat = decode_out_region(layer.program, image_mem)
            sems.append(decode_layer_output(layer, out_mat))
        return sems[-1]

    def serve(self, images, *, backend: str = "batched", fault_hook=None,
              count_overflows: bool = False, guard=None):
        """Compile-once/serve-many batched inference (DESIGN.md §Batching).

        ``images`` is a batch of requests (see :meth:`_as_image_list`).
        The whole batch moves through the layer chain together: one
        ``(batch, nbytes)`` DRAM stack, one batched VTA execution per
        layer over the layer's cached instruction plan, vectorised OUT
        decoding, and per-request host reshaping between layers.  Outputs
        are bit-identical to calling :meth:`serve_one` per request — the
        batch axis only amortises instruction decode and merges the
        per-instruction array work.

        ``backend="batched"`` (default) runs the vectorised instruction
        interpreter; ``backend="pallas"`` executes each layer as a fused
        MXU kernel call over the whole batch, sending the layer's
        activations and staging A on the device, with no DRAM stack
        (:func:`repro.core.pallas_backend.serve_layer`; interpreted only
        on the CPU) — bit-identical to the simulators.

        Returns ``(stacked outputs, per-layer batch-total reports)``: the
        leading output axis is the request index.

        ``guard`` (a :class:`repro.harden.GuardPolicy`) routes the batch
        through the integrity-guarded path and returns ``(outputs,
        reports, guard_reports)`` with one :class:`GuardReport` per
        request (DESIGN.md §Hardening).
        """
        if guard is not None:
            if backend != "batched":
                raise CompileError(
                    "guarded serving runs on the batched instruction "
                    "interpreter (its watchdog and injection hooks are "
                    "per-instruction); drop guard= or backend="
                    f"{backend!r}", constraint="serve-guard-backend")
            from repro.harden import guards as _guards
            return _guards.guarded_serve(self, images, guard,
                                         fault_hook=fault_hook)
        if backend not in SERVE_BACKENDS:
            raise CompileError(
                f"serve supports backend in {SERVE_BACKENDS} (the "
                f"per-image backends {SERVE_ONE_BACKENDS} are "
                f"serve_one()'s), got {backend!r}",
                constraint="serve-backend")
        imgs = self._as_image_list(images)
        rows = len(imgs)
        with span("vta.serve", rows=rows):
            return self._serve_stack(imgs, backend, fault_hook,
                                     count_overflows)

    def _fresh_stack(self, rows: int) -> np.ndarray:
        """A ``(rows, nbytes)`` stack holding :meth:`dram_image` in every
        row.  Each thread reuses the largest stack it has served this
        network with: rewriting memory already mapped is a copy, where a
        new stack also faults every page in (1.06 GB for ResNet-50's
        batch of 8).  Only the calling thread ever sees its stack, and
        :meth:`serve` returns copies, never views of it."""
        held = self.__dict__.setdefault("_stacks", threading.local())
        size = self.allocator.image_size()
        buf = getattr(held, "stack", None)
        if buf is None or len(buf) < rows:
            buf = held.stack = np.empty((rows, size), dtype=np.uint8)
        stack = buf[:rows]
        stack[0] = 0
        for layer in self.layers:
            layer.program.place_segments(stack[0])
        stack[1:] = stack[0]
        return stack

    def _serve_stack(self, imgs: List[np.ndarray], backend: str, fault_hook,
                     count_overflows: bool):
        """:meth:`serve`'s layer loop, with a span at each boundary
        (``vta.layer`` > ``vta.stage``, the backend's own spans,
        ``vta.readout``).  ``batched`` runs each layer over one ``(batch,
        nbytes)`` DRAM stack; ``pallas`` sends each layer's activations to
        the kernel, which stages A on the device, and keeps no DRAM image
        (:func:`~repro.core.pallas_backend.serve_layer`)."""
        rows = len(imgs)
        stack = None
        if backend == "batched":
            with span("vta.stage"):
                stack = self._fresh_stack(rows)
        elif fault_hook is not None:
            raise ValueError(
                "fault_hook requires per-instruction execution; the pallas "
                "backend has no instruction stream to hook (use "
                "backend='oracle'/'fast'/'batched' for injection)")
        reports: List[SimReport] = []
        all_sems: List[np.ndarray] = []         # per layer: (B, ...) stack
        srcs, rsrcs = self._sources(), self._res_sources()
        for k, layer in enumerate(self.layers):
            with span("vta.layer", layer=k, useful_macs=rows * layer.macs):
                src_sems = (imgs if k == 0 or srcs[k] < 0
                            else all_sems[srcs[k]])
                res_sems = None
                if rsrcs[k] is not None:
                    res_sems = (imgs if rsrcs[k] < 0
                                else all_sems[rsrcs[k]])
                if backend == "pallas":
                    out_mats, report = self._pallas_layer(layer, src_sems,
                                                          res_sems)
                else:
                    stack, report = self._batched_layer(
                        stack, layer, src_sems, res_sems,
                        self._layer_hook(fault_hook, k), count_overflows)
                reports.append(report)
                with span("vta.readout"):
                    if stack is not None:
                        out_mats = decode_out_region_batch(layer.program,
                                                           stack)
                    all_sems.append(decode_layer_output_batch(
                        layer, out_mats, kept=stack is None))
        return np.array(all_sems[-1]), reports

    def _batched_layer(self, stack: np.ndarray, layer: CompiledLayer,
                       src_sems, res_sems, hook, count_overflows: bool):
        """One layer on the batched interpreter: stage the layer's INP (and
        RES) bytes into ``stack``, run its cached plan over every row."""
        from .fast_simulator import BatchFastSimulator, plan_for
        with span("vta.stage"):
            self._stage_layer_input_batch(stack, layer, src_sems)
            if res_sems is not None:
                self._stage_residual_batch(stack, layer, res_sems)
        # the loop owns ``stack`` and re-reads it from ``sim.dram``, so the
        # engine's defensive copy is skipped
        sim = BatchFastSimulator(self.config, stack, copy_dram=False,
                                 count_overflows=count_overflows)
        report = sim.run(layer.program.instructions,
                         plan=plan_for(layer.program), fault_hook=hook)
        return sim.dram, report

    def _pallas_layer(self, layer: CompiledLayer, src_sems, res_sems):
        """One layer on the pallas kernel: stack the layer's semantic
        inputs (and a join's skip activations) and run it; the result
        comes back as ``(B, M, N)``, only the rows a pooled layer keeps."""
        from .pallas_backend import serve_layer
        with span("vta.stage"):
            x = np.asarray(src_sems, dtype=np.int8)
            x = x[:, 0] if layer.spec.kind == "conv" else \
                x.reshape(len(x), -1)
            res = None
            if res_sems is not None:
                res = np.asarray(res_sems, dtype=np.int8)
                res = res[:, 0] if res.ndim == 5 else res
        return serve_layer(layer, x, res)


def calibrate_network(specs: Sequence[LayerSpec],
                      images: Sequence[np.ndarray], *,
                      margin: int = 1, saturate: bool = False
                      ) -> Tuple[List[int], List[List[np.ndarray]]]:
    """Static per-layer requant shifts from a calibration set (§4.2
    discipline: shifts are fixed at compile time; the margin bit guards
    unseen inputs against int8 wrap-around).  Model-agnostic: works for
    any conv/fc chain with valid or same padding and avg/max pooling.

    Layer k's input depends on shifts < k, so calibration is sequential,
    and the images advance through each layer under the *device's*
    requant semantics (:func:`repro.core.layout.requant_int8` — wrap by
    default, clip under ``saturate=True``), with pinned
    ``spec.requant_shift`` values honoured exactly as :func:`compile_layer`
    honours them.  Anything else calibrates downstream layers against
    activations the machine never produces (DESIGN.md §Quantization).

    Returns ``(shifts, traces)`` where ``traces[k][i]`` is layer ``k``'s
    semantic output for calibration image ``i`` — bit-identical to what
    ``serve``/``serve_one`` produce for the same image, which the
    calibration-drift regression test asserts differentially.
    """
    from .conv_lowering import mat2tensor
    from .layer_compiler import (choose_requant_shift, layer_matrices,
                                 pool_divisor, pool_plan_for,
                                 reference_layer_acc)
    from .layout import exact_matmul
    from .layout import requant_int8

    shifts: List[int] = []
    traces: List[List[np.ndarray]] = []
    currents = [np.asarray(img, np.int8) for img in images]
    for spec in specs:
        pool_div = 0
        accs = []
        geos = []
        for cur in currents:
            A, B, geo = layer_matrices(spec, cur)
            plan = pool_plan_for(spec, geo)
            pool_div = pool_divisor(plan)
            accs.append(reference_layer_acc(exact_matmul(A, B), spec.bias,
                                            spec.relu, plan))
            geos.append((geo, plan))
        if spec.requant_shift is not None:
            shift = spec.requant_shift
        else:
            stacked = np.concatenate([a.reshape(-1) for a in accs])
            shift = choose_requant_shift(stacked,
                                         already_shifted=pool_div) + margin
        shifts.append(shift)
        # advance every calibration image through this layer
        nxt = []
        for acc, (geo, plan) in zip(accs, geos):
            out = requant_int8(acc >> (pool_div + shift), saturate=saturate)
            if spec.kind == "conv":
                oh = plan.out_h if plan else geo.out_h
                ow = plan.out_w if plan else geo.out_w
                nxt.append(mat2tensor(out, oh, ow))
            else:
                nxt.append(out)
        currents = nxt
        traces.append(list(currents))
    return shifts, traces


def calibrate_network_shifts(specs: Sequence[LayerSpec],
                             images: Sequence[np.ndarray],
                             margin: int = 1, *,
                             saturate: bool = False) -> List[int]:
    """Shift list only — see :func:`calibrate_network` (which also
    returns the per-layer calibration trace)."""
    return calibrate_network(specs, images, margin=margin,
                             saturate=saturate)[0]


def compile_network(specs: Sequence[LayerSpec], input_tensor: np.ndarray, *,
                    cfg: Optional[VTAConfig] = None,
                    dram_offset: int = 0,
                    schedule: str = "serialized") -> NetworkProgram:
    """Compile a network: every layer against one shared DRAM allocation,
    each layer's input taken from the previous layer's reference output."""
    cfg = cfg or vta_default()
    alloc = DramAllocator(offset=dram_offset, page_bytes=cfg.page_bytes)
    layers: List[CompiledLayer] = []
    current: np.ndarray = np.asarray(input_tensor, dtype=np.int8)
    for spec in specs:
        layer = compile_layer(spec, current, cfg=cfg, allocator=alloc,
                              schedule=schedule)
        layers.append(layer)
        # Reference output becomes the next layer's input (semantic form).
        ref = layer.ref_output_matrix
        if spec.kind == "conv":
            from .conv_lowering import mat2tensor
            current = mat2tensor(ref, layer.out_h, layer.out_w)
        else:
            current = ref
    return NetworkProgram(config=cfg, allocator=alloc, layers=layers,
                          input_tensor=np.asarray(input_tensor))
