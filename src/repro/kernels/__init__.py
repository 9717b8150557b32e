"""Pallas TPU kernels for episode counting — the paper's GPGPU mining
loop re-derived for the TPU VPU with the paper's *two-axis*
computation-to-core mapping: episodes on lanes/sublanes (grid axis 0,
parallel) and the **time axis on grid axis 1** — event chunks for the
PTPE kernels, time segments for the MapConcatenate kernels — with
``arbitrary`` (sequential) semantics carrying state across steps.

Modules:
  a1_count — bounded-list Algorithm 1 (``a1_count_kernel``), its
    state-in/state-out streaming variant (``a1_count_state_kernel``: the
    (NP, LCAP, BM) timestamp brick, one-hot write-pointer mask, and
    count/ovf rows are kernel I/O with in-place aliasing), and the
    segment-parallel ``a1_mapconcat_kernel`` (§5.2.2: each grid step runs
    K = N phase-shifted machines over one segment's event window and
    folds its (a, count, b) tuple onto the carried Concatenate state —
    Map + Concatenate fused into one launch).
  a2_count — single-slot Algorithm 3 (``a2_count_kernel``), the
    streaming analogue (``a2_count_state_kernel``), and the single-slot
    segmented variant (``a2_mapconcat_kernel``) used by the two-pass
    cull.
  ops — dispatch policy (TPU compiled / interpret mode / decline to the
    XLA scans), host↔kernel layout contract (``episode_layout``,
    ``event_brick``, ``a1_state_layout``/``a1_state_unpack``,
    ``a2_state_layout``/``a2_state_unpack``, ``mapconcat_layout``,
    ``segment_bricks``), the instrumented entry points (``a1_state_call``,
    ``a2_state_call``, ``a1_mapconcat_tuples``/``a2_mapconcat_tuples``,
    ``a1_mapconcat_count``/``a2_mapconcat_count``, vmapped fused variants
    for the cross-session batcher), and the one-shot wrappers.
  ref — pure-jnp layout oracles the interpret-mode tests pin the kernels
    against.

Event streaming: the stream is never broadcast whole. Event bricks are
blocked on the second grid axis (``block_e`` events per step, default
``DEFAULT_BLOCK_E``) and copied per step into **SMEM**, where the kernels
read each event as a scalar (Mosaic has no scalar load from VMEM at a
dynamic lane), while the machine state lives in output blocks revisited
across the axis — fresh-state and state-carried wrappers share the same
chunked launch. Segmented kernels block by time segment and, within a
segment, by event chunk (a third grid axis over the
(types/times/dup/τ_p/τ_{p+1}) brick), with the phase machines carried
across chunks in VMEM scratch. Inside every kernel, state is held as
(NP, BM) rows — the level edge gate is a sublane rotate plus an iota
select and the last-level row a static slice — since Mosaic refuses
scatters (``.at[].set``) and boolean sublane concatenation.

Layout contract for the carried state (see ``ops``): episode-major host
state (``core.count_a1.A1State`` [M, N, L] / ``core.count_a2.A2State``
[M, N]) packs to level-major lane/sublane bricks — s (NP, LCAP, MP),
po one-hot (NP, LCAP, MP), cnt/ovf (8, MP) with row 0 meaningful —
padded with TIME_NEG_INF / PAD_ROW_TYPE so padded lanes and rows are
inert. Chunked carried calls are bit-identical to one call on the
concatenation (A1 additionally requires chunk boundaries not to split
timestamp tie groups; ``core.streaming.StreamingCounter`` holds back the
trailing tie group to guarantee that). The segmented kernels share their
phase starts (``core.mapconcat.phase_cum``), stitch zones
(``stitch_zones``), and fold semantics (``fold_pair_unrolled``) with the
XLA MapConcatenate so the two paths cannot drift.
"""
