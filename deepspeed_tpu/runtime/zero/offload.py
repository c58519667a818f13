"""ZeRO-Offload integration (host master params + native CPU-Adam).

See csrc/adam/cpu_adam.cpp and ops/adam/cpu_adam.py for the native step.
Counterpart of ref `stage2.py:743-941,1416-1427`.

Where the offload step is transfer-bound (a slow host link; its share
of a step is not measured on the current installation), the remaining
lever is bytes on the wire. `zero_optimization.
offload_wire` configures a compressed wire format for the round trip:

  D2H  grad_bits=8  — int8 with one fp32 scale per 4096-element block
       (~2x over the bf16 wire, ~4x over fp32);
       grad_bits=1  — sign bits + per-block scale with error feedback
       (the 1-bit Adam compression, runtime/fp16/onebit_adam.py's
       pack_signs/compress applied to the offload wire; ~16x over
       bf16). The error-feedback residual lives on device next to the
       grads and carries quantization error into the next step.
  H2D  param_bits=8 — int8 param-DELTA against a persistent
       device-resident fp32 param copy; the host keeps a shadow of that
       copy (equal to it up to float rounding — XLA may fuse the
       dequant multiply-add), so the delta quantization error feeds
       back through the next delta and the device copy cannot drift
       from the masters. Costs 4 bytes/param of extra device memory.
  warmup_steps     — first N successful steps run an uncompressed fp32
       wire (both directions) so error feedback starts from a settled
       trajectory — the fp32-warmup window of 1-bit Adam (Tang et al.).

grad_bits=32 / param_bits=32 (the defaults) run the legacy wire
code-path unchanged: bf16 grads down when computing in bf16 (fp32
otherwise), fused bf16 params back.
"""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime.fp16.loss_scaler import (
    make_static_loss_scale_state)
from deepspeed_tpu.runtime.utils import _zeros_like_f32
from deepspeed_tpu.utils.logging import log_dist


def quantize_int8_blocks(x, block):
    """Symmetric int8 block quantization of a flat fp32 array: returns
    (q int8 [n], scales fp32 [ceil(n/block)]) with scale = max-abs/127
    per block. The ONE numpy expression of the wire's quantization
    contract (the jitted grad_tail_q8 is its jnp twin); dequant is
    q * scales[i // block]."""
    n = x.size
    nb = -(-n // block)
    pad = np.zeros(nb * block, np.float32)
    pad[:n] = x
    blocks = pad.reshape(nb, block)
    s = (np.abs(blocks).max(axis=1) / 127.0).astype(np.float32)
    safe = np.where(s > 0, s, 1.0).astype(np.float32)
    q = np.clip(np.rint(blocks / safe[:, None]), -127, 127).astype(
        np.int8)
    return q.reshape(-1)[:n], s


class ZeroOffloadMixin:
    """ZeRO-Offload: fp32 master params + Adam moments live in host RAM,
    stepped by the native CPU-Adam (`csrc/adam/cpu_adam.cpp`); the device
    holds only compute-dtype params and the fp32 grad accumulator.

    Counterpart of ref `stage2.py:743-941,1416-1427` (pinned-buffer grad
    offload + CPUAdam step + fused fp16 param copy-back): here the jitted
    step produces one flat fp32 grad vector, the host applies AdamW and
    downcasts to bf16 in the same native pass, and a single device_put
    returns the updated params — XLA pipelines the transfers that the
    reference overlaps with CUDA streams. The optional compressed wire
    (module docstring) shrinks both directions of that round trip.
    """

    def _offload_enabled(self):
        return bool(self.zero_optimization() and self.zero_cpu_offload())

    def _init_offload(self, params_f32):
        from jax.flatten_util import ravel_pytree
        from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam
        from deepspeed_tpu.runtime.fp16.loss_scaler import CreateLossScaler

        flat, self._offload_unravel = ravel_pytree(params_f32)
        self._host_master = np.asarray(jax.device_get(flat),
                                       dtype=np.float32).copy()
        # host-side unravel metadata (leaf offsets in ravel_pytree
        # order): lets the checkpoint writer rebuild the module tree
        # from the host masters without a device round trip
        leaves, treedef = jax.tree_util.tree_flatten(params_f32)
        offs, off = [], 0
        for leaf in leaves:
            shape = tuple(np.shape(leaf))
            offs.append((off, shape))
            off += int(np.prod(shape))
        self._offload_np_meta = (treedef, offs)
        p = dict(self._config.optimizer_params or {})
        betas = p.get("betas", (0.9, 0.999))
        self._host_adam = DeepSpeedCPUAdam(
            flat.size,
            lr=p.get("lr", 1e-3),
            betas=betas,
            eps=p.get("eps", 1e-8),
            weight_decay=p.get("weight_decay", 0.0),
            adamw_mode=p.get("adam_w_mode", True) or
            (self._config.optimizer_name or "").lower() == C.ADAMW_OPTIMIZER)
        self._host_scaler = CreateLossScaler(
            dtype_fp16=self.fp16_mode,
            static_loss_scale=self._config.loss_scale,
            dynamic_scaling=self.dynamic_loss_scale_enabled,
            dynamic_loss_args=self.dynamic_loss_scale_args())
        self._init_offload_wire(int(flat.size))
        # memory ledger: the offload design MOVES the master/optimizer
        # state to host RAM — the ledger's host space is where ZeRO-
        # Offload's whole memory argument lives, so register it there
        from deepspeed_tpu.monitor import memory as _mem
        led = self.monitor.ledger
        led.register(_mem.CAT_HOST_MASTER, "offload.host_master",
                     self._host_master.nbytes, space=_mem.SPACE_HOST)
        # CPU-Adam moments: exp_avg + exp_avg_sq, fp32, one per element
        led.register(_mem.CAT_HOST_OPT, "offload.adam_moments",
                     2 * int(flat.size) * 4, space=_mem.SPACE_HOST)
        log_dist(
            f"ZeRO-Offload: {flat.size/1e6:.1f}M fp32 masters + moments "
            f"on host (native cpu_adam={self._host_adam.native}, "
            f"wire grad_bits={self._wire_grad_bits} "
            f"param_bits={self._wire_param_bits})", ranks=[0])

    # Chunk size is capped in BYTES (fp32 elements x4), not in chunk
    # count: D2H(i+1) / CPU-Adam(i) / H2D(i-1) only overlap if each
    # chunk stays small relative to the whole model, so large models get
    # proportionally more chunks (a fixed chunk COUNT would mean ~500 MB
    # chunks on a 1B-param model and no real pipelining). 16 MB fp32 is
    # big enough to amortize per-transfer dispatch.
    _OFFLOAD_CHUNK_ELEMS = 4 << 20

    # Elements per quantization scale group (compressed wire). A multiple
    # of 8 so 1-bit sign packing stays byte-aligned at block edges; 4096
    # keeps the fp32-scale overhead at 0.1% of the int8 payload.
    _OFFLOAD_WIRE_BLOCK = 4096

    def _offload_bounds(self, n, align=1):
        k = max(1, -(-n // self._OFFLOAD_CHUNK_ELEMS))
        edges = np.linspace(0, n, k + 1).astype(np.int64)
        if align > 1:
            # quantized wires slice per-block scales by absolute offset,
            # so interior chunk edges must sit on block boundaries
            edges = (edges // align) * align
            edges[-1] = n
        return [(int(edges[i]), int(edges[i + 1])) for i in range(k)
                if edges[i + 1] > edges[i]]

    def _init_offload_wire(self, n):
        zc = self._config.zero_config
        self._wire_grad_bits = zc.offload_wire_grad_bits
        self._wire_param_bits = zc.offload_wire_param_bits
        self._wire_warmup = zc.offload_wire_warmup_steps
        self._offload_wire_steps = 0
        self.wire_stats = {}
        B = self._OFFLOAD_WIRE_BLOCK
        align = B if self._wire_grad_bits in (1, 8) else 1
        self._offload_bounds_cached = self._offload_bounds(n, align)
        self._offload_grad_residual = None
        self._offload_param_shadow = None
        self._offload_device_flat = None
        from deepspeed_tpu.monitor import memory as _mem
        led = self.monitor.ledger
        if self._wire_grad_bits == 1:
            # error-feedback residual: device-resident, padded to a
            # whole number of scale blocks, same layout as the flat
            # grad wire it corrects
            n_pad = -(-n // B) * B
            self._offload_grad_residual = jnp.zeros((n_pad,), jnp.float32)
            led.register(_mem.CAT_WIRE, "offload.grad_residual",
                         self._offload_grad_residual.nbytes)
        if self._wire_param_bits == 8:
            # host shadow tracks the device fp32 flat copy (both apply
            # the SAME dequantized deltas; they agree to float rounding).
            # copy=True is load-bearing: on the CPU backend jnp.asarray
            # may ALIAS the numpy buffer, and _host_master is mutated
            # in place by every CPU-Adam step
            self._offload_param_shadow = self._host_master.copy()
            self._offload_device_flat = jnp.array(self._host_master,
                                                  copy=True)
            led.register(_mem.CAT_WIRE, "offload.param_shadow",
                         self._offload_param_shadow.nbytes,
                         space=_mem.SPACE_HOST)
            # the persistent device fp32 flat copy IS the int8 wire's
            # documented 4 B/param device cost — ledger it so an OOM
            # dump can name it
            led.register(_mem.CAT_WIRE, "offload.device_flat",
                         self._offload_device_flat.nbytes)

    def _build_offload_fns(self):
        """Jitted halves of the offload step."""
        clip = self.gradient_clipping()
        B = self._OFFLOAD_WIRE_BLOCK

        def unscale_clip(acc_grads, loss_scale):
            from jax.flatten_util import ravel_pytree
            flat, _ = ravel_pytree(acc_grads)
            flat = flat / loss_scale
            norm = jnp.sqrt(jnp.vdot(flat, flat))
            if clip and clip > 0:
                factor = jnp.minimum(1.0, clip / (norm + 1e-6))
                factor = jnp.where(jnp.isfinite(factor), factor, 1.0)
                flat = flat * factor
            return flat, norm

        def grad_tail(acc_grads, loss_scale):
            flat, norm = unscale_clip(acc_grads, loss_scale)
            # bf16 on the wire when computing in bf16: halves D2H bytes
            # (the reference likewise offloads fp16 grads to pinned host
            # buffers, ref stage2.py:743-941); the host re-expands to
            # fp32 before CPU-Adam. Unscale/clip above stay fp32.
            # grad_bits=16 forces the bf16 wire for fp16/fp32 compute.
            if self.compute_dtype == jnp.bfloat16 or \
                    self._wire_grad_bits == 16:
                flat = flat.astype(jnp.bfloat16)
            return flat, norm

        self._offload_grad_tail_jit = jax.jit(grad_tail)

        def _pad_to_blocks(flat):
            pad = (-flat.shape[0]) % B
            if pad:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad,), flat.dtype)])
            return flat.reshape(-1, B)

        if self._wire_grad_bits == 8:
            def grad_tail_q8(acc_grads, loss_scale):
                flat, norm = unscale_clip(acc_grads, loss_scale)
                n = flat.shape[0]
                blocks = _pad_to_blocks(flat)
                scale = jnp.max(jnp.abs(blocks), axis=1) / 127.0
                safe = jnp.where(scale > 0, scale, 1.0)
                q = jnp.clip(jnp.round(blocks / safe[:, None]),
                             -127, 127).astype(jnp.int8)
                # the block-padding tail never crosses the wire
                return q.reshape(-1)[:n], scale, norm

            self._offload_grad_tail_q_jit = jax.jit(grad_tail_q8)
        elif self._wire_grad_bits == 1:
            from deepspeed_tpu.runtime.fp16.onebit_adam import pack_signs

            def grad_tail_q1(acc_grads, loss_scale, residual):
                """Sign+scale compression with error feedback — the
                worker-side compress() of onebit_adam applied to the
                offload wire. The residual is NOT committed here: the
                host assigns it only on non-overflow steps, so a skipped
                step cannot pollute the feedback loop. Pad lanes (block
                round-up past n) are masked out of both the residual and
                the final block's scale: they never cross the wire, so
                residual left in them would recirculate forever and a
                mean over them would deflate the real elements' scale."""
                flat, norm = unscale_clip(acc_grads, loss_scale)
                n = flat.shape[0]
                corrected = _pad_to_blocks(flat) + residual.reshape(-1, B)
                lane = jnp.arange(corrected.size).reshape(-1, B)
                mask = (lane < n).astype(jnp.float32)
                corrected = corrected * mask
                scale = jnp.sum(jnp.abs(corrected), axis=1) / \
                    jnp.sum(mask, axis=1)
                signs = jnp.where(corrected >= 0, 1.0, -1.0)
                new_res = ((corrected - scale[:, None] * signs) *
                           mask).reshape(-1)
                # bytes covering real elements only; B % 8 == 0 keeps
                # chunk slices byte-aligned
                packed = pack_signs(corrected.reshape(-1))[: -(-n // 8)]
                return packed, scale, norm, new_res

            self._offload_grad_tail_q_jit = jax.jit(grad_tail_q1)

        if self._wire_grad_bits in (1, 8, 16) and self._wire_warmup > 0:
            def grad_tail_warm(acc_grads, loss_scale):
                # fp32 wire during the warmup window (no downcast at all
                # — grad_bits=16's forced bf16 cast included)
                return unscale_clip(acc_grads, loss_scale)

            self._offload_grad_tail_warm_jit = jax.jit(grad_tail_warm)

        def rebuild_params(chunks):
            # chunk tuple (compute dtype or fp32) -> param tree
            flat = jnp.concatenate([c.reshape(-1) for c in chunks])
            tree = self._offload_unravel(flat.astype(jnp.float32))
            tree = jax.tree_util.tree_map(
                lambda x: x.astype(self.compute_dtype), tree)
            return jax.lax.with_sharding_constraint(
                tree, self._param_pspecs_cached)

        self._offload_rebuild_jit = jax.jit(rebuild_params)

        if self._wire_param_bits == 8:
            bounds = self._offload_bounds_cached

            def flat_to_tree(flat):
                tree = self._offload_unravel(flat)
                tree = jax.tree_util.tree_map(
                    lambda x: x.astype(self.compute_dtype), tree)
                return jax.lax.with_sharding_constraint(
                    tree, self._param_pspecs_cached)

            def rebuild_qdelta(device_flat, q_chunks, s_chunks):
                """int8 delta chunks -> new fp32 flat + param tree. The
                per-element dequant (q * scale[block]) mirrors the
                host's shadow update, keeping device_flat == shadow up
                to float rounding (XLA may fuse the mul+add)."""
                deltas = []
                for (lo, hi), q, s in zip(bounds, q_chunks, s_chunks):
                    d = q.astype(jnp.float32) * \
                        jnp.repeat(s, B)[: hi - lo]
                    deltas.append(d)
                new_flat = device_flat + jnp.concatenate(deltas)
                return new_flat, flat_to_tree(new_flat)

            self._offload_rebuild_qdelta_jit = jax.jit(rebuild_qdelta)

            def rebuild_sync(chunks):
                # fp32 full-sync push (warmup window): also refreshes
                # the device-resident flat copy
                new_flat = jnp.concatenate(
                    [c.reshape(-1) for c in chunks]).astype(jnp.float32)
                return new_flat, flat_to_tree(new_flat)

            self._offload_rebuild_sync_jit = jax.jit(rebuild_sync)

    def _zero_acc(self):
        """Fresh grad accumulator with the engine's shardings (a plain
        jnp.zeros would change input shardings and force a recompile)."""
        return jax.device_put(_zeros_like_f32(self.state.acc_grads),
                              self._acc_shardings)

    def _offload_unravel_np(self, flat):
        """Host twin of `_offload_unravel`: the fp32 module tree as
        numpy VIEWS of `flat` (ravel_pytree leaf order) — no device
        round trip on the checkpoint path."""
        treedef, offs = self._offload_np_meta
        leaves = [flat[off:off + int(np.prod(shape))].reshape(shape)
                  for off, shape in offs]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _offload_checkpoint_snapshot(self, isolate=True):
        """Checkpoint-snapshot half for offload state: copies of
        everything the next host Adam step mutates in place (masters,
        moments, wire shadow) plus the wire residual/step counter.
        Taken synchronously — offload runs a sync loop, and a host
        memcpy is cheap next to serialization.  isolate=False (inline
        writes, which finish before the next step can mutate anything)
        skips the copies and hands out live references — the legacy
        sync path's memory profile."""
        master = self._host_master.copy() if isolate else \
            self._host_master
        adam_sd = self._host_adam.state_dict()
        if isolate:
            # copy every array state_dict returns — a key whitelist
            # would silently drop state the sync path keeps
            adam_sd = {k: v.copy() if isinstance(v, np.ndarray) else v
                       for k, v in adam_sd.items()}
        snap = {
            "host_master": master,
            "host_adam": adam_sd,
            # module leaves are views of `master` — consistent with it
            # by construction, and free of extra host RAM
            "module": self._offload_unravel_np(master),
        }
        if self._config.zero_config.offload_wire_compressed():
            snap["offload_wire"] = self._offload_wire_state_dict()
        return snap

    def _offload_wire_state_dict(self):
        """Wire state that must survive a checkpoint: the error-feedback
        residual and the param shadow (the device flat copy is the
        shadow's mirror and is rebuilt from it on load)."""
        d = {"wire_steps": np.asarray(self._offload_wire_steps, np.int64)}
        if self._offload_grad_residual is not None:
            d["grad_residual"] = np.asarray(
                jax.device_get(self._offload_grad_residual))
        if self._offload_param_shadow is not None:
            d["param_shadow"] = self._offload_param_shadow.copy()
        return d

    def _offload_wire_load_state_dict(self, sd):
        if not sd:
            # checkpoint written without wire state (or with a different
            # wire config): error feedback safely restarts from zero and
            # the shadow resyncs to the restored masters
            if self._offload_grad_residual is not None:
                self._offload_grad_residual = jnp.zeros_like(
                    self._offload_grad_residual)
            if self._offload_param_shadow is not None:
                self._offload_param_shadow[:] = self._host_master
                # copy=True: jnp.asarray may alias the mutated buffer
                self._offload_device_flat = jnp.array(self._host_master,
                                                      copy=True)
            return
        self._offload_wire_steps = int(sd.get("wire_steps", 0))
        if self._offload_grad_residual is not None:
            if "grad_residual" in sd and \
                    sd["grad_residual"].shape == \
                    self._offload_grad_residual.shape:
                self._offload_grad_residual = jnp.asarray(
                    sd["grad_residual"])
            else:
                # checkpoint from a different wire config (e.g. int8):
                # error feedback restarts from zero, NOT from whatever
                # this engine accumulated before the load
                self._offload_grad_residual = jnp.zeros_like(
                    self._offload_grad_residual)
        if self._offload_param_shadow is not None:
            if "param_shadow" in sd and \
                    sd["param_shadow"].shape == \
                    self._offload_param_shadow.shape:
                self._offload_param_shadow[:] = sd["param_shadow"]
            else:
                self._offload_param_shadow[:] = self._host_master
            # copy=True: jnp.asarray may alias the mutated buffer
            self._offload_device_flat = jnp.array(
                self._offload_param_shadow, copy=True)

    def _offload_in_warmup(self):
        return (self._wire_warmup > 0 and
                self._offload_wire_steps < self._wire_warmup)

    def _offload_take_step(self, lr):
        """Host half: fetch clipped grads, CPU-Adam, push params."""
        import time as _time
        _t0 = _time.perf_counter()
        B = self._OFFLOAD_WIRE_BLOCK
        # warmup only means something for legs that compress; with a
        # fully native wire (32/32) wire_stats must not claim a warmup
        warm = self._offload_in_warmup() and (
            self._wire_grad_bits in (1, 8, 16) or
            self._wire_param_bits == 8)
        # effective wire modes this step (0 = dense/legacy leg)
        g_mode = self._wire_grad_bits \
            if self._wire_grad_bits in (1, 8) and not warm else 0
        p_mode = 8 if self._wire_param_bits == 8 else 0

        new_res = None
        if g_mode == 1:
            packed, g_scales, norm, new_res = \
                self._offload_grad_tail_q_jit(
                    self.state.acc_grads, self.state.scale.loss_scale,
                    self._offload_grad_residual)
        elif g_mode == 8:
            qflat, g_scales, norm = self._offload_grad_tail_q_jit(
                self.state.acc_grads, self.state.scale.loss_scale)
        elif warm and self._wire_grad_bits in (1, 8, 16):
            flat, norm = self._offload_grad_tail_warm_jit(
                self.state.acc_grads, self.state.scale.loss_scale)
        else:
            flat, norm = self._offload_grad_tail_jit(
                self.state.acc_grads, self.state.scale.loss_scale)
        norm_host = float(jax.device_get(norm))
        # feeds the monitor (grad_norm metric + stall diagnosis): the
        # offload step is the one host-synchronous engine path, so the
        # norm is already on host for free
        self._offload_last_norm = norm_host
        self.monitor.heartbeat("offload")
        overflow = not np.isfinite(norm_host)
        self._host_scaler.update_scale(overflow)
        new_scale = make_static_loss_scale_state(
            self._host_scaler.cur_scale) if self.fp16_mode else \
            self.state.scale

        if overflow:
            # skipped step: the error-feedback residual computed above is
            # DISCARDED (never assigned), masters/shadow untouched
            self.state = self.state._replace(
                scale=new_scale,
                acc_grads=self._zero_acc(),
                skipped=self.state.skipped + 1)
            self.monitor.subsystem_span(
                "offload", "host_step (overflow skip)", _t0,
                _time.perf_counter() - _t0)
            return True
        if new_res is not None:
            self._offload_grad_residual = new_res

        # Chunk-pipelined host step (the stream overlap of ref
        # stage2.py:743-941): all chunk D2H copies start async up
        # front; while chunk i runs CPU-Adam, chunk i+1's download is
        # in flight and chunk i-1's upload (async device_put inside
        # jnp.asarray) is draining — D2H / compute / H2D overlap
        # without threads.
        bounds = self._offload_bounds_cached
        if g_mode == 1:
            wire_chunks = [packed[lo // 8: -(-hi // 8)]
                           for lo, hi in bounds]
            d2h_bytes = packed.nbytes + g_scales.nbytes
        elif g_mode == 8:
            wire_chunks = [qflat[lo:hi] for lo, hi in bounds]
            d2h_bytes = qflat.nbytes + g_scales.nbytes
        else:
            wire_chunks = [flat[lo:hi] for lo, hi in bounds]
            d2h_bytes = flat.nbytes
        for c in wire_chunks:
            c.copy_to_host_async()
        if g_mode in (1, 8):
            g_scales_np = np.asarray(g_scales)

        self._host_adam.begin_step()
        out_chunks = []
        q_out, s_out = [], []
        h2d_bytes = 0
        for (lo, hi), c in zip(bounds, wire_chunks):
            mchunk = self._host_master[lo:hi]
            # fused native chunk step + bf16 downcast in one pass when
            # the device consumes bf16 and the param wire is native
            bf16_out = np.empty(hi - lo, np.uint16) \
                if p_mode == 0 and self.compute_dtype == jnp.bfloat16 \
                else None
            if g_mode == 1:
                self._host_adam.step_chunk_q1(
                    lo, hi, mchunk, np.asarray(c),
                    g_scales_np[lo // B: -(-hi // B)], B, lr=lr,
                    params_bf16_out=bf16_out)
            elif g_mode == 8:
                self._host_adam.step_chunk_q8(
                    lo, hi, mchunk, np.asarray(c),
                    g_scales_np[lo // B: -(-hi // B)], B, lr=lr,
                    params_bf16_out=bf16_out)
            else:
                # fetch in the wire dtype (bf16 when computing bf16),
                # THEN widen on host — np.asarray(c, dtype=f32) could
                # upcast device-side and transfer twice the bytes
                g_np = np.asarray(c).astype(np.float32, copy=False)
                self._host_adam.step_chunk(
                    lo, hi, mchunk, g_np, lr=lr,
                    params_bf16_out=bf16_out)

            if p_mode == 8 and not warm:
                # int8 delta against the shadow; the dequantized delta
                # is applied to the shadow so its quantization error
                # feeds back through the NEXT delta (no drift)
                delta = mchunk - self._offload_param_shadow[lo:hi]
                q, s = quantize_int8_blocks(delta, B)
                dd = q.astype(np.float32) * \
                    np.repeat(s, B)[:hi - lo]
                self._offload_param_shadow[lo:hi] += dd
                qc = jnp.asarray(q)
                sc = jnp.asarray(s)
                q_out.append(qc)
                s_out.append(sc)
                h2d_bytes += qc.nbytes + sc.nbytes
            elif p_mode == 8:
                # warmup: full-precision sync keeps shadow == device
                self._offload_param_shadow[lo:hi] = mchunk
                out = jnp.asarray(mchunk.copy())
                out_chunks.append(out)
                h2d_bytes += out.nbytes
            elif bf16_out is not None:
                out = jnp.asarray(bf16_out).view(jnp.bfloat16)
                out_chunks.append(out)
                h2d_bytes += out.nbytes
            else:
                # fp16/fp32 compute: push fp32 masters, cast on device
                # (a bf16 round-trip would truncate fp16's mantissa)
                out = jnp.asarray(mchunk.copy())
                out_chunks.append(out)
                h2d_bytes += out.nbytes

        if p_mode == 8 and not warm:
            self._offload_device_flat, new_params = \
                self._offload_rebuild_qdelta_jit(
                    self._offload_device_flat, tuple(q_out), tuple(s_out))
        elif p_mode == 8:
            self._offload_device_flat, new_params = \
                self._offload_rebuild_sync_jit(tuple(out_chunks))
        else:
            new_params = self._offload_rebuild_jit(tuple(out_chunks))

        self._offload_wire_steps += 1
        n = self._host_master.size
        native_elem = 2 if self.compute_dtype == jnp.bfloat16 else 4
        self.wire_stats = {
            "grad_bits": self._wire_grad_bits,
            "param_bits": self._wire_param_bits,
            "warmup": bool(warm),
            "d2h_bytes": int(d2h_bytes),
            "h2d_bytes": int(h2d_bytes),
            # what the uncompressed (legacy) wire moves per step, for
            # reduction ratios without a second engine
            "d2h_bytes_native": int(n * native_elem),
            "h2d_bytes_native": int(n * native_elem),
        }
        self.state = self.state._replace(
            params=new_params,
            scale=new_scale,
            acc_grads=self._zero_acc(),
            global_steps=self.state.global_steps + 1)
        # the one host-synchronous engine path gets its own Perfetto
        # track: D2H + chunked CPU-Adam + H2D as a single slice
        self.monitor.subsystem_span(
            "offload", "host_step", _t0, _time.perf_counter() - _t0,
            args={"d2h_bytes": int(d2h_bytes),
                  "h2d_bytes": int(h2d_bytes)})
        return False
