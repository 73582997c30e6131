"""The Communicator plugin boundary, TPU-native.

The reference's central abstraction (SURVEY.md §2,
``src/communicator.hpp``: virtual ``initialize / send / recv / waitall /
finalize`` with NCCL and UCX implementations, MPI bootstrap) is the seam
`BASELINE.json`'s north star requires us to keep. On TPU the seam moves
up one level of abstraction:

- the reference's per-peer ``send/recv`` pairs + ``waitall`` always
  implement one logical op — an all-to-all exchange — so the TPU
  ``Communicator`` exposes ``all_to_all`` directly and lets XLA lower it
  to ICI DMAs (there is no profitable TPU analog of hand-posted sends);
- ``initialize`` becomes mesh construction + (multi-host) the JAX
  distributed runtime handshake — coordinator over TCP/DCN replaces the
  reference's ``MPI_Bcast`` of the NCCL unique id (SURVEY.md §3.3);
- ``waitall`` disappears: the collective completes inside the compiled
  program with no user-visible handle. (Whether the compiler ALSO
  overlaps it with compute is an empirical question — measured in
  round 2: on the v5e toolchain it does not; see docs/OVERLAP.md.)

Implementations:

- :class:`TpuCommunicator` — ``jax.lax.all_to_all`` under ``shard_map``
  over a 1-D mesh; works identically on a real ICI slice and on the
  CPU fake backend (``--xla_force_host_platform_device_count=N``).
- :class:`LocalCommunicator` — 1 rank; the collective degenerates to
  identity. BASELINE config 1's CPU reference path.

``spmd`` is the entry for running a per-rank function SPMD over the
mesh, with row-sharded inputs/outputs; the orchestrator
(:mod:`distributed_join_tpu.parallel.distributed_join`) is built on it.
"""

from __future__ import annotations

import abc
import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_join_tpu import device
from distributed_join_tpu.parallel.mesh import (
    make_hierarchical_mesh,
    make_mesh,
)


# The TPU ragged-all-to-all pads each moved row to this many lanes.
_RAGGED_LANES = 128


def _pvary(x, axis_name):
    """Mark ``x`` varying over ``axis_name`` for shard_map's vma
    checker; already-varying inputs pass through (``lax.pcast``
    refuses a varying -> varying cast)."""
    if axis_name in jax.typeof(x).vma:
        return x
    return lax.pcast(x, axis_name, to="varying")


class Communicator(abc.ABC):
    """Abstract communication backend (the reference's plugin boundary)."""

    name: str = "abstract"

    @property
    @abc.abstractmethod
    def n_ranks(self) -> int:
        ...

    @abc.abstractmethod
    def all_to_all(self, x: jax.Array) -> jax.Array:
        """Exchange row-blocks: x has shape (n_ranks * m, ...) per rank;
        block i (rows [i*m, (i+1)*m)) is sent to rank i; the result
        concatenates the blocks received from every rank in rank order.
        Must be called inside :meth:`spmd`. Shape-preserving."""

    def ppermute_all_to_all(self, x: jax.Array) -> jax.Array:
        """:meth:`all_to_all` semantics with an async-schedulable
        lowering where the backend has one (TpuCommunicator chains
        collective-permutes, docs/OVERLAP.md); the default — plain
        all_to_all — is always semantically correct."""
        return self.all_to_all(x)

    @abc.abstractmethod
    def all_gather(self, x: jax.Array) -> jax.Array:
        """Concatenate every rank's ``x`` along axis 0 (rank order),
        replicated to all ranks. Must be called inside :meth:`spmd`.
        The skew path broadcasts heavy-hitter build rows with this —
        the TPU analog of the reference replicating hot partitions."""

    def all_gather_replicated(self, x: jax.Array) -> jax.Array:
        """:meth:`all_gather` whose result shard_map's vma checker
        knows to be replicated, so it may leave the program through a
        replicated output (the telemetry tape)."""
        return self.all_gather(x)

    @abc.abstractmethod
    def spmd(self, fn: Callable, *, sharded_out=None) -> Callable:
        """Compile ``fn`` to run SPMD, one instance per rank.

        Array args/outputs are row-sharded over ranks (global view);
        outputs flagged replicated in ``sharded_out`` (a pytree prefix of
        bools, default all-sharded) must be identical on every rank
        (e.g. after a psum).
        """

    def axis_index(self):
        """This rank's index, traced (0 on a single-rank backend)."""
        return jnp.int32(0)

    def ragged_all_to_all(self, operand, output, input_offsets,
                          send_sizes, output_offsets, recv_sizes):
        """Exact-size exchange (the reference's offsets+sizes send/recv
        loop as ONE op): peer i receives ``operand[input_offsets[i] :
        + send_sizes[i]]`` written at ``output_offsets[i]`` of its
        ``output`` buffer. All size/offset vectors are (n_ranks,) int32
        and must be mutually consistent across ranks (see
        parallel/shuffle.ragged_plan). Must be called inside
        :meth:`spmd`. Returns the filled output buffer."""
        return self._ragged_emulate(
            operand, output, input_offsets, send_sizes,
            output_offsets, recv_sizes,
        )

    def pvary(self, x):
        """Mark ``x`` as varying over the rank axis for shard_map's
        vma checker (identity on single-rank backends)."""
        return x

    # -- hierarchical seams (two-level ICI/DCN shuffle) ---------------
    #
    # A flat communicator is the degenerate one-slice hierarchy: the
    # whole mesh is one ICI domain, so the slice-local exchange IS the
    # global all_to_all and the cross-slice exchange is the identity.
    # Multi-slice backends (HierarchicalTpuCommunicator) override all
    # three; docs/HIERARCHY.md has the routing algebra.

    @property
    def n_slices(self) -> int:
        """Slow-tier (DCN) groups in the mesh; 1 = no slow tier."""
        return 1

    @property
    def chips_per_slice(self) -> int:
        """Fast-tier (ICI) ranks per slice."""
        return self.n_ranks

    def all_to_all_chip(self, x: jax.Array) -> jax.Array:
        """:meth:`all_to_all` restricted to THIS rank's slice (the
        fast ICI tier): x has ``chips_per_slice`` leading blocks;
        block j goes to the j-th chip of this slice. Flat backends:
        the slice is the whole mesh."""
        return self.all_to_all(x)

    def all_to_all_slice(self, x: jax.Array) -> jax.Array:
        """:meth:`all_to_all` across slices at a FIXED chip index
        (the slow DCN tier): x has ``n_slices`` leading blocks; block
        t goes to this chip's peer on slice t. Flat backends: one
        slice, identity."""
        return x

    # Emulation of ragged_all_to_all for backends/platforms without the
    # hardware op (XLA:CPU has no ragged-all-to-all thunk). Assembles
    # the output from all-gathered operands with static-shape masked
    # copies — wire-inefficient by construction, but bit-identical in
    # semantics; tests and the virtual-device mesh run through it.
    def _ragged_emulate(self, operand, output, input_offsets, send_sizes,
                        output_offsets, recv_sizes):
        n = self.n_ranks
        me = self.axis_index()
        g_op = self.all_gather(operand[None, ...])        # (n, len, ...)
        g_in = self.all_gather(input_offsets[None, :])    # (n, n)
        g_sz = self.all_gather(send_sizes[None, :])
        g_out = self.all_gather(output_offsets[None, :])
        out = output
        idx = jnp.arange(output.shape[0], dtype=jnp.int32)
        for j in range(n):
            in_off = g_in[j, me]
            sz = g_sz[j, me]
            out_off = g_out[j, me]
            rel = idx - out_off
            take = (rel >= 0) & (rel < sz)
            src = g_op[j][
                jnp.clip(in_off + rel, 0, operand.shape[0] - 1)
            ]
            mask = take.reshape((-1,) + (1,) * (out.ndim - 1))
            out = jnp.where(mask, src, out)
        return out

    # -- small conveniences shared by backends ------------------------

    def psum(self, x):
        return x

    def finalize(self) -> None:
        """Reference parity (``Communicator::finalize``); no-op — XLA
        owns transport/buffer lifetime on TPU."""


class TpuCommunicator(Communicator):
    """XLA-collective backend over a 1-D device mesh (ICI data plane)."""

    name = "tpu"

    def __init__(self, mesh: Mesh | None = None, n_ranks: int | None = None):
        self.mesh = mesh if mesh is not None else make_mesh(n_ranks)
        if len(self.mesh.axis_names) != 1:
            raise ValueError("TpuCommunicator needs a 1-D mesh")
        self.axis_name = self.mesh.axis_names[0]

    @property
    def n_ranks(self) -> int:
        return self.mesh.shape[self.axis_name]

    def all_to_all(self, x: jax.Array) -> jax.Array:
        return lax.all_to_all(
            x, self.axis_name, split_axis=0, concat_axis=0, tiled=True
        )

    def all_gather(self, x: jax.Array) -> jax.Array:
        return lax.all_gather(x, self.axis_name, axis=0, tiled=True)

    def all_gather_replicated(self, x: jax.Array) -> jax.Array:
        # The same all-gather HLO; JAX 0.9 exports no public spelling
        # of the invariant-typed form.
        from jax._src.lax.parallel import all_gather_invariant

        return all_gather_invariant(x, self.axis_name, axis=0,
                                    tiled=True)

    def ppermute_all_to_all(self, x: jax.Array) -> jax.Array:
        """``all_to_all`` semantics via a chain of n-1
        ``collective-permute`` steps (plus the local block).

        Same result as :meth:`all_to_all` for x of shape (n, ...);
        the point is the LOWERING: round 2 measured that grouped
        ``all-to-all`` HLO is emitted synchronously on this toolchain
        (zero async pairs, docs/OVERLAP.md), while collective-permute
        lowers as start/done pairs that XLA's latency-hiding
        scheduler can interleave with unrelated compute — the
        reference's stream-pipelined shuffle (SURVEY.md §2
        "Over-decomposition") expressed in XLA terms.

        Step d: every rank s sends block x[(s+d) % n] to rank
        (s+d) % n, so this rank (r) receives sender (r-d) % n's
        block. The d-ordered stack is sender-rotated; one reversed
        dynamic roll restores sender order.
        """
        n = self.n_ranks
        r = self.axis_index()
        parts = []
        for d in range(n):
            piece = lax.dynamic_index_in_dim(
                x, (r + jnp.int32(d)) % n, axis=0, keepdims=False
            )
            if d:
                piece = lax.ppermute(
                    piece, self.axis_name,
                    perm=[(s, (s + d) % n) for s in range(n)],
                )
            parts.append(piece)
        stacked = jnp.stack(parts)      # index d = sender (r-d) % n
        # out[s] = stacked[(r-s) % n]: reverse then roll by r+1
        return jnp.roll(stacked[::-1], r + 1, axis=0)

    def axis_index(self):
        return lax.axis_index(self.axis_name)

    def pvary(self, x):
        return _pvary(x, self.axis_name)

    def ragged_all_to_all(self, operand, output, input_offsets,
                          send_sizes, output_offsets, recv_sizes):
        plan = (input_offsets, send_sizes, output_offsets, recv_sizes)
        if not device.on_tpu():
            # XLA:CPU has no ragged-all-to-all thunk.
            return self._ragged_emulate(operand, output, *plan)
        return self._ragged_tpu(
            operand, output, plan,
            functools.partial(lax.ragged_all_to_all,
                              axis_name=self.axis_name))

    def _ragged_tpu(self, operand, output, plan, exchange):
        """Route one column through the TPU's ragged-all-to-all.

        The op moves rows, and pads each row to 128 lanes: a 1-D
        column's rows are single elements, so it would move and hold
        128x the column (the 50M-row four-chip join ran out of HBM
        compiling it, PR 21). 1-D columns therefore go through
        :meth:`_ragged_lane_dense`; 64-bit integers ride as two uint32
        words (the x64 rewriter has no 64-bit op) and narrow types as
        int32. ``exchange`` is the raw op (the emulation in tests)."""
        dt = operand.dtype
        if dt.itemsize == 8 and jnp.issubdtype(dt, jnp.floating):
            # f64: neither the 64-bit op nor an f64 bitcast exists on
            # TPU. The emulation is correct but all-gathers the whole
            # column — MORE wire bytes than the padded shuffle; warn
            # (once per trace) so the regression is never silent.
            import warnings

            warnings.warn(
                "ragged_all_to_all: f64 operands fall back to an "
                "all-gather emulation on TPU, which moves MORE bytes "
                "than the padded shuffle; keep f64 columns on "
                "shuffle='padded'",
                stacklevel=3,
            )
            return self._ragged_emulate(operand, output, *plan)
        if dt.itemsize == 8:
            u = operand.astype(jnp.uint64)
            out_u = output.astype(jnp.uint64)
            words = [
                self._ragged_tpu(
                    (u >> jnp.uint64(s)).astype(jnp.uint32),
                    (out_u >> jnp.uint64(s)).astype(jnp.uint32),
                    plan, exchange,
                ).astype(jnp.uint64) << jnp.uint64(s)
                for s in (0, 32)
            ]
            return (words[0] | words[1]).astype(dt)
        if operand.ndim > 1:
            # Row = the trailing dims: lane-padded only where they are
            # narrower than 128 (the fixed-width string columns).
            return exchange(operand, output, *plan)
        if dt.itemsize < 4:
            return self._ragged_tpu(
                operand.astype(jnp.int32), output.astype(jnp.int32),
                plan, exchange,
            ).astype(dt)
        return self._ragged_lane_dense(operand, output, plan, exchange)

    def _ragged_lane_dense(self, operand, output, plan, exchange):
        """A 1-D 32-bit ragged exchange through (groups, 128) rows.

        Every (sender, destination) block starts on a 128-element
        boundary of the send and receive buffers, so the op moves whole
        lane-dense rows; at most 127 pad elements ride per block. The
        blocks move into and out of that layout with one rolled copy
        per peer (a shift, not a gather)."""
        input_offsets, send_sizes, output_offsets, _ = plan
        lanes = _RAGGED_LANES
        n = self.n_ranks
        me = self.axis_index()
        dt = operand.dtype
        # Every sender's sizes and output offsets (row = sender): the
        # receiver's block positions follow from the sizes alone.
        g = self.all_gather(
            jnp.concatenate([send_sizes, output_offsets])[None, :])
        sizes, offs = g[:, :n], g[:, n:]
        groups = (sizes + (lanes - 1)) // lanes
        at = jnp.cumsum(groups, axis=0) - groups   # [s, d] in groups
        g_send = groups[me]
        a_send = jnp.cumsum(g_send) - g_send

        n_send = -(-operand.shape[0] // lanes) + n
        x = jnp.pad(operand, (0, n_send * lanes - operand.shape[0]))
        pos = jnp.arange(n_send * lanes, dtype=jnp.int32)
        send = jnp.zeros_like(x)
        for d in range(n):
            start = a_send[d] * lanes
            keep = (pos >= start) & (pos < start + send_sizes[d])
            send = jnp.where(keep, jnp.roll(x, start - input_offsets[d]),
                             send)
        n_recv = -(-output.shape[0] // lanes) + n
        recv = exchange(
            send.reshape(n_send, lanes),
            self.pvary(jnp.zeros((n_recv, lanes), dt)),
            a_send, g_send, at[me], groups[:, me],
        ).reshape(-1)

        m = output.shape[0]
        pos = jnp.arange(m, dtype=jnp.int32)
        out = output
        for s in range(n):
            o, r = offs[s, me], sizes[s, me]
            keep = (pos >= o) & (pos < o + r)
            out = jnp.where(keep, jnp.roll(recv, o - at[s, me] * lanes)[:m],
                            out)
        return out

    def psum(self, x):
        return lax.psum(x, self.axis_name)

    def spmd(self, fn: Callable, *, sharded_out=None) -> Callable:
        shard_spec = P(self.axis_name)
        if sharded_out is None:
            out_specs = shard_spec
        else:
            out_specs = jax.tree.map(
                lambda rep: P() if rep else shard_spec,
                sharded_out,
            )
        mapped = jax.shard_map(
            fn, mesh=self.mesh, in_specs=shard_spec, out_specs=out_specs
        )
        return jax.jit(mapped)

    def device_put_sharded(self, tree):
        """Place a pytree of host arrays row-sharded over the mesh.

        Multi-host (``jax.process_count() > 1``): every process passes
        the same GLOBAL value (deterministic generators make this free)
        and keeps only its addressable shards — the multi-controller
        contract. Device-backed leaves are pulled to host first;
        ``device_put`` requires addressable-only sources there.
        """
        sharding = NamedSharding(self.mesh, P(self.axis_name))
        if jax.process_count() > 1:
            import numpy as np

            tree = jax.tree.map(np.asarray, tree)
        return jax.tree.map(lambda a: jax.device_put(a, sharding), tree)


class HierarchicalTpuCommunicator(TpuCommunicator):
    """XLA-collective backend over a 2-D ``(slice, chip)`` mesh — the
    8->64-chip scale-out topology (ROADMAP item 5, docs/HIERARCHY.md).

    The flat rank space is slice-major: rank ``r`` lives at
    ``(r // chips_per_slice, r % chips_per_slice)``, so a row-sharded
    table shards identically to the 1-D mesh over the same device
    order. Global collectives run over BOTH axes (multi-axis
    ``lax.all_to_all``/``all_gather`` concatenate slice-major — flat
    rank order, so every existing call site is semantics-identical);
    the hierarchical seams expose the per-tier collectives the
    two-level shuffle routes through: :meth:`all_to_all_chip` inside
    a slice over ICI, :meth:`all_to_all_slice` across slices over
    DCN.
    """

    name = "tpu-hier"

    def __init__(self, mesh: Mesh | None = None,
                 n_slices: int | None = None,
                 n_ranks: int | None = None):
        if mesh is None:
            mesh = make_hierarchical_mesh(n_slices or 1, n_ranks)
        if len(mesh.axis_names) != 2:
            raise ValueError(
                "HierarchicalTpuCommunicator needs a 2-D (slice, "
                f"chip) mesh, got axes {mesh.axis_names}")
        self.mesh = mesh
        self.slice_axis, self.chip_axis = mesh.axis_names
        # Both axes, slice-major — every inherited collective
        # (all_to_all/all_gather/psum/axis_index) runs globally over
        # the tuple and sees the flat rank space.
        self.axis_name = (self.slice_axis, self.chip_axis)

    @property
    def n_ranks(self) -> int:
        return self.n_slices * self.chips_per_slice

    @property
    def n_slices(self) -> int:
        return self.mesh.shape[self.slice_axis]

    @property
    def chips_per_slice(self) -> int:
        return self.mesh.shape[self.chip_axis]

    def all_to_all_chip(self, x: jax.Array) -> jax.Array:
        return lax.all_to_all(
            x, self.chip_axis, split_axis=0, concat_axis=0, tiled=True
        )

    def all_to_all_slice(self, x: jax.Array) -> jax.Array:
        return lax.all_to_all(
            x, self.slice_axis, split_axis=0, concat_axis=0,
            tiled=True
        )

    def axis_index(self):
        return (lax.axis_index(self.slice_axis)
                * jnp.int32(self.chips_per_slice)
                + lax.axis_index(self.chip_axis))

    def pvary(self, x):
        return _pvary(_pvary(x, self.slice_axis), self.chip_axis)

    def ppermute_all_to_all(self, x: jax.Array) -> jax.Array:
        """The 1-D ppermute chain is a flat-mesh lowering; on the
        hierarchical mesh the async-schedulable story is the
        two-level shuffle itself, so this degrades to the grouped
        global all_to_all (always semantically correct)."""
        return self.all_to_all(x)


class LocalCommunicator(Communicator):
    """Single-rank backend: collectives are identities. This is the
    reference's 1-rank path (BASELINE config 1)."""

    name = "local"

    @property
    def n_ranks(self) -> int:
        return 1

    def all_to_all(self, x: jax.Array) -> jax.Array:
        return x

    def all_gather(self, x: jax.Array) -> jax.Array:
        return x

    def spmd(self, fn: Callable, *, sharded_out=None) -> Callable:
        return jax.jit(fn)

    def device_put_sharded(self, tree):
        return jax.tree.map(jax.device_put, tree)


def make_communicator(name: str, n_ranks: int | None = None,
                      n_slices: int | None = None) -> Communicator:
    """Factory keyed by the reference driver's ``--communicator`` flag.

    The reference accepts {NCCL, UCX}; this framework adds ``tpu`` (the
    north-star flag) and ``local``. NCCL/UCX are recognized but rejected
    with an explanatory error — there is no NCCL/UCX on TPU hardware.

    ``n_slices`` (the drivers' ``--slices K``) > 1 builds the 2-D
    hierarchical mesh (docs/HIERARCHY.md); 1/None keeps the flat 1-D
    mesh — deliberately NOT a one-row hierarchical mesh, so the
    degenerate hierarchy lowers byte-identically to the seed programs.
    """
    lname = name.lower()
    if lname == "tpu":
        if n_slices is not None and n_slices > 1:
            return HierarchicalTpuCommunicator(n_slices=n_slices,
                                               n_ranks=n_ranks)
        return TpuCommunicator(n_ranks=n_ranks)
    if lname == "local":
        if n_slices is not None and n_slices > 1:
            raise ValueError(
                "the local (1-rank) communicator has no multi-slice "
                "topology; --slices needs --communicator=tpu")
        return LocalCommunicator()
    if lname in ("nccl", "ucx"):
        raise ValueError(
            f"communicator {name!r} is the reference's GPU backend; "
            "this framework targets TPU — use --communicator=tpu"
        )
    raise ValueError(f"unknown communicator {name!r}")
