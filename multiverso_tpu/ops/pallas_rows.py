"""Pallas TPU kernels for sparse row Get/Add on HBM-resident tables.

Replaces XLA's scatter/gather on the MatrixTable row path (reference hot
path: per-row ``updater_->Update`` loops, ``src/table/matrix_table.cpp:
387-417``; worker scatter-back ``317-341``). XLA lowers `data.at[ids].add`
to a serialized scatter (~µs per row); these kernels instead issue a group
of row DMAs per grid step so the row-fetch latencies overlap, turning the
op bandwidth-bound.

Contracts (enforced by the caller, `tables.matrix_table.MatrixServer`):

* ids are int32 in ``[0, table_rows)`` — pad slots point at the table's
  sentinel scratch row (never a live row) with zero deltas.
* for ``scatter_add_rows`` the *live* ids are unique within the call
  (duplicates pre-combined); pad slots may repeat the sentinel because a
  zero delta leaves its bytes unchanged, so racing identical writes are
  benign.
* the number of ids is a multiple of the row group (bucket sizes are
  powers of two ≥ the group).
* for ``scatter_add_rows`` the ids may outnumber the delta's rows: the
  DELTA sizes the grid (``ceil(rows / ROW_GROUP)`` steps, a static shape),
  so the tail of a bucket is never read, moved or written. Only the slots
  of the last group past the delta's end are still walked; the kernel
  gives them a zero delta whatever the block holds there, so they must
  aim at the sentinel (or at any row the call does not name).
* with ``tail_count`` the delta may instead outnumber the rows named (a
  caller's buffer of one shape, every call another count of rows: PR 38):
  the last id slot holds the count of leading slots that name rows, the
  kernel reads it from SMEM, and a grid step past the count issues no
  descriptor; the last step under the count walks its live slots in a loop.
  The operands are the plain call's (no second upload, one custom call of
  one name to a trace) and one program serves every count under a shape.
* ``sign`` (a static float of the table's updater: -1.0 for SGD) scales
  the delta inside the kernel; the cast to the table's dtype is inside the
  same jitted program. A delta narrower than the table's lanes (300 columns
  into 384) is taken as it is: its block is as wide as the delta and the
  kernel adds the last lane tile under a static lane mask. XLA would pad
  it in a pass of its own (it will not fuse the pad into the copy that
  turns a column-major 100,000 x 300 device array row-major: 0.45 ms each
  on the v5e, my chip run, PR 26). A row Add is one device program.
* the table's width is any whole number of 128-lane tiles, ``T``. A table
  of one tile is taken as it is: a row is 512 contiguous bytes. A wider
  float32 table lies in HBM as (8, 128) tiles, ``T`` of them side by side
  for every 8 rows, so a row is ``T`` runs of 512 bytes, 4 KB apart; the
  kernels see it as ``(rows / 8, T, 8, 128)`` (:func:`_tile_view`, a
  bitcast to XLA: no byte moves, the table's layout is what it was) and
  move a row with ONE strided descriptor of ``T x 512`` bytes. Its row
  count must then be a multiple of 8 (the caller pads; the tiles are
  there in HBM either way).

* ``add_at_lanes`` (PR 42; PR 41 built it and was refused for what it cost
  a process's set-up; since PR 49 it works a caller's rule out itself;
  since PR 51 it walks ROWS, not slots) is the update of SINGLE float32
  values of lane-dense 1-D states (the keyed FTRL table's ``z`` and ``n``)
  by the same row descriptors: a state is read as rows of 128, a key lives
  in row ``key >> 7``, lane ``key & 127``, and the rule (``step``, plain
  ``jax.numpy``: the FTRL table hands it ``ftrl_table.ftrl_step``; without
  one, ``state[key] += delta``) is traced into the kernel and computed on
  the ``(LANE_GROUP, 128)`` blocks the rows landed in, so that the
  caller's program gathers no state and writes no block a slot for the
  kernel. This module knows nothing of FTRL. Its contract is not
  ``scatter_add_rows``': the keys are SORTED ascending; keys that share a
  row are expected, and the row is read ONCE, stepped once and written
  once: the distinct rows are compacted on the device in front of the
  kernel (their list, their count ``U`` and, for each group of 128 of
  them, the chunks of 128 sorted slots that hold its keys: the scalar
  prefetch), a grid step walks ``LANE_GROUP`` ROWS and ``ceil(U /
  LANE_GROUP)`` steps are live (the grid is the slots', the bound; a step
  past ``U`` does nothing), and what a step's slots bring is folded onto
  its rows in VMEM by the matrix unit, the delta's four bytes as int8
  planes between two one-hots, summed in int32, exactly; a key several slots name is stepped once,
  by its first slot; pad slots may aim at a row that live keys share (the
  FTRL table's scratch key does) and bring nothing; a lane no stepping
  slot names is written back as read, by a select (the rule is computed on
  it and dropped; adding ``-0.0`` would do for every float32 but a
  denormal, which the vector unit flushes: PR 41's first build's test
  found it); the slots, filled to whole groups here, are at most
  ``PREFETCH_SLOTS``. ``U`` is what adapts: the program reads it from its
  input and returns it. Its docstring has the rest.
* nothing that ``import multiverso_tpu`` reaches imports this module at its
  top: it brings ``jax.experimental.pallas``, a second of module code. A
  table imports it inside the functions that need it; the keyed FTRL table
  loads it on a thread under the fill of its state
  (``tables/ftrl_table.py``; ``tests/test_ftrl_keyed.py`` holds both).

Interpret mode is the caller's explicit choice, made once from the
platform of the devices that hold the table (:func:`interpret_for`): ``cpu``
interprets (the test mesh), ``tpu`` compiles, anything else is an error.

Optimization record (measured at PR 5 on a v5e, single core, 1024-row x
128-col update on a 1M-row table, scan-slope timing; not re-measured on the
current machine):

* group-size sweep: 8→83us, 16→49us, 32→32us, 64→26.4us, 128→27.2us;
  256 exceeds the semaphore-flag memory (sflag 2KB): that kernel kept a
  semaphore a slot each way. The 64-group asymptote was read as the
  per-row DMA issue cost (~13ns/descriptor on the scalar core), not
  transfer latency. Superseded by the PR 34 sweep below (two semaphores a
  kernel, 100,000 rows): a descriptor costs 8.2 ns, a grid step 0.4-0.5 us
  on top, and 64 rows a step left a third of a launch in the steps.
* software pipelining (double-buffered scratch, group g+1 reads overlapped
  with group g writes): 35.8us — SLOWER than the simple kernel. Two causes:
  the dynamic buffer indexing taxes every descriptor, and the overlap
  window (one group's processing, <1us) barely covers a write's latency.
  A read-only variant measures 18.2us vs 26.4us read+write, i.e. the write
  phase already overlaps ~70% behind the next group's reads via the DMA
  engine's own queueing. The simple kernel is kept.
* remaining headroom would need fewer/larger descriptors (rows are 512B —
  per-descriptor cost dominates); with arbitrary row ids there is no
  contiguity to merge. That floor is PER SLOT, and says nothing of which
  slots a launch walks: see PR 25 below.
* descriptor coalescing (r3): sorted-unique ids do contain contiguous runs
  on zipf workloads, so a variant merges each all-consecutive 4-row segment
  into ONE 4-row DMA. Measured (1M×128 table, 1024-id batches,
  scan-slope): simple 27.2-27.3µs vs coalesced 36.5-39.6µs on BOTH
  sorted-zipf and sorted-uniform ids — a 34-45% LOSS. Two reasons, both
  structural: (a) zipf-1024-of-1M contiguity is only 13% of segments (the
  dense head of the distribution is ~100 ids; the tail is sparse), and
  (b) the per-segment `pl.when` pair costs ~12.6µs/call on the scalar core
  (64 conditionals: 16 segments × read/write × start/wait) while the best
  possible descriptor saving is 96 × ~13ns ≈ 1.2µs even at 100%
  contiguity. Conclusion: on v5e the branch cost exceeds the descriptor
  cost by ~10×, so run-merging cannot win at 512B rows regardless of
  workload. The coalesced kernel was deleted; this paragraph is its record.
* the grid follows the delta (PR 25, 2026-09-28, v5e, the benchmark's
  `emb128.bulk-rows` cell: a device Add of 100,000 rows x 128 float32 into
  a 10,000,000-row table, ids in a 131,072 bucket; device time of the
  kernel's events in a 4 s traced window). Before, the grid came from the
  ids: 2,048 steps, 3.351 ms a launch, 31,072 slots of it (486 whole
  groups) sentinel pads, with a pad and a multiply program of 0.18 and
  0.20 ms in front. Now 1,563 steps over the delta's 100,032 slots:
  2.451 ms a launch, nothing in front. That is 24.5 ns a slot against
  25.6: the pad slots cost MORE than live ones (the 64 descriptors of an
  all-sentinel group write one row), so a quarter of the slots was 27% of
  the time. A slot's cost is asked of live slots only since (100,032
  launched for 100,000 named); the 24.5 ns themselves stood until PR 34
  (17.3 ns at one tile, 20.4 at three: below).
* rows wider than one lane tile (PR 26). What Mosaic said to each form,
  compiled for a described v5e (libtpu 0.0.34), 3,000,008 x 384 float32:
  a one-row slice of the table as XLA holds it, ``(1, 384)`` or ``(1, 128)``
  of ``memref<3000008x384xf32, #tpu.tiled<(8,128),[3,1]>>``: "Slice shape
  along dimension 0 must be aligned to tiling (8), but is 1" (at 128 lanes
  the same slice passes: an (8, 128)-tiled array one tile wide is
  row-major). A table re-laid as ``(rows x T, 128)`` or as ``T`` planes
  would compile, and would cost every reader of ``[row, column]``
  (``get_device``, dense ops, updaters, checkpoints) a copy of the table.
  The tile view needs neither: ``(rows / 8, T, 8, 128)`` is the same bytes,
  XLA lowers the reshape and transpose on both sides of the call to
  ``bitcast`` (0 bytes of temporaries, the table still aliased in place),
  and Mosaic takes ``[rid >> 3, :, rid & 7, :]`` either as ``T``
  descriptors of ``(1, 128)`` or as one of ``(T, 1, 128)``. The second is
  what runs: on the v5e, 100,000 rows of 384 lanes, 8.16 ms a launch as
  three descriptors a row, 3.86 as one with ``//`` and ``%``, 2.85 with
  the shift and the mask (28 ns a slot; 24.5 at one tile); 256 and 512
  lanes cost what 384 do. PERF.md, Findings, PR 26.
* two semaphores a kernel and 256 rows a grid step (PR 34, 2026-09-29, one
  v5e chip, `TPU v5 lite`; 100,000 distinct Zipf rows in a 131,072 bucket
  into the cells' two tables, 10,000,001 x 128 and 3,000,008 x 384 with a
  300-column delta; device ms a launch, the kernel's events in a trace of
  nine calls after a warm one, every variant in one chip call, each result
  checked against numpy: the rows named and every column's sum).
  A DMA semaphore counts bytes, so a group's row copies signal ONE
  semaphore a direction and one wait whose descriptor spans the whole
  scratch block takes the group's bytes off it.
    variant                          128 lanes      384 lanes
    PR 33's kernel, group 64          2.4513         2.8053
    one wait a group, group 64        2.2835         2.6189
    a wait a slot on the one
      semaphore, group 64             2.2832         2.6179
    one wait a group, group 128       1.8978         2.2456
    group 256 (kept)                  1.7308         2.0461
    group 512                         1.6556         2.0728
    group 1024                        1.6420         2.0764
    write-backs awaited a step late on two scratch blocks named by the
      step's parity under `pl.when` (group 64 / 128 / 256 / 512 / 1024):
                       2.1688 / 1.8759 / 1.7323 / 1.6658 / 1.6429
                       2.5484 / 2.2112 / 2.0457 / 2.0376 / 2.0566
    each id read from SMEM once, kept in a register for the write-back
      (group 64 / 128 / 256 / 512 / 1024):
                       2.5704 / 2.2652 / 2.1089 / 2.0438 / 2.0174
                       2.8257 / 2.5081 / 2.3502 / 2.3412 / 2.3808
  What it says. (a) The WAIT costs nothing: 128 waits a group on one
  semaphore time like one wait (2.2832 against 2.2835). What the 128
  semaphores cost was their addresses: 0.168 ms a launch, 0.8 ns a
  descriptor, not the 5-6 ns ISSUE 34 inferred. One wait a group is kept
  because it is the shorter program (and two semaphores lift the limit on
  the group). (b) The rest is the grid step: 64 -> 128 rows a step saves
  0.386 ms over 782 fewer steps, 0.49 us a step (0.43 from 128 to 256, 0.38
  from 256 to 512): what a step spends with nothing to issue, from its
  last read issued to its landing and around the delta block's turn (not
  the write-backs' landing: (c)), which more rows a step amortise. The
  asymptote is 1.64 ms, 16.4 ns a slot, 8.2 ns a descriptor issued by the
  scalar core. (c) Awaiting the write-backs a step late hides 0.07 us a
  step at group 64 and nothing from 128 up: NOT kept. Ids kept in
  registers spill: a loss at every group. (d) The two widths want
  different groups by less than 5% of a launch (128 lanes: 512 over 256 by
  4.3%; 384 lanes: 256 over 512 by 1.3%), so ONE constant, 256: 17.3 ns a
  slot at one tile (24.5 before), 20.4 at three (28.0). 512 would save
  0.075 ms more at 128 lanes and cost 0.027 at 384, a minimum bucket of
  512 slots and half the width the VMEM budget admits. Left: reading group
  g+1's rows while group g is added (two blocks by parity) could take at
  most the 0.09 ms between group 256 and the asymptote.
* single floats by row descriptors, `add_at_lanes` (2026-10-01, one v5e
  chip, `TPU v5 lite`; PR 41 built and priced it at a group of 256 and was
  refused for `setup_s`; PR 42 the same day gave it a group of its own. The
  keyed FTRL Add of the benchmark's `ftrlctr.step-keys`: 111,244 Zipf keys
  of a 16,384-sample step in 67,555 rows of 128, sorted, 114,696 live slots
  of a 131,072 bucket, into `z` and `n` of 882,775,040 float32 each (3.53
  GB); ms a launch, 30 launches back to back after a warm one, wall clock
  over the count; PR 42's chip runs where a line says nothing, PR 41's
  where it says so; the kernel's path against XLA's scatters bit for bit
  over six Adds at the full key space, a key's slots either side of a grid
  step's boundary among them, in the same call: 0 entries differ).
    the table's program, XLA's two scatters (ledger, PR 40)      23.672
    the table's program, `add_at_lanes` at a group of 128 (kept)   5.650
      of it in front of the kernel (sort, gathers, the step)       1.386
      `add_at_lanes` alone (the rows it takes made + the kernel)   4.285
    the group, `add_at_lanes` alone, three rounds 64 128 256 / 256 128 64 /
    64 128 256, and the table's program's seconds to `.lower()` on the
    chip's host, twice (XLA's own program 0.037-0.089):
      64    4.728 / 4.725 / 4.726 ms    0.184 / 0.208 s
      128   4.287 / 4.282 / 4.285 ms    0.277 / 0.260 s   (kept)
      256   4.311 / 4.312 / 4.305 ms    0.460 / 0.437 s
    at 256, PR 41: without `_run_and` (wrong on shared rows: a price) 3.955
    at 256, PR 41: `_scatter_add` twice on delta rows (wrong there)   4.304
    at 256, PR 41: its descriptors issued from a loop of 256 / k passes
      of k slots (k = 1 / 2 / 4 / 8 / 16 / 32 / 64; unrolled 4.336):
                 8.053 / 7.815 / 7.221 / 6.928 / 6.779 / 6.704 / 6.666
    at 256, PR 41: unrolled as it is lowered, `fori_loop(unroll=True)` 4.337
  What it says. (a) The price follows the rows named, not the operand:
  459,264 descriptors (two states, a read and a write-back a slot) in 3.96
  ms (the custom call in the cell's traced run) are 8.6 ns each, PR 34's asymptote (8.2) at 3.53 GB as at 5.1 GB. (b)
  **The group is set by what it costs to lower, not by the device**: 128
  reads 0.02-0.03 ms UNDER 256 in every round (four descriptors a slot
  leave a grid step's 0.45 us a twentieth of its issue time; the merge is
  thirteen shifts of three `(128, 128)` blocks a group where PR 41's was
  sixteen of `(256, 128)`), 64 reads 0.44 ms over (897 more steps, and
  the merge no longer hides under the issue), and the program lowers in
  0.26-0.28 s at 128 against 0.44-0.46 at 256, paid in every process's
  warm-up before any cache is asked: the unrolled slots are the cost, XLA's
  own program lowers in 0.04-0.09. The standing kernels keep `ROW_GROUP`
  256 (two descriptors a slot: PR 34's sweep). (c) Merging the slots of a
  row costs 0.38 ms (PR 41, at 256), partly under the descriptors' issue;
  40% of the slots share a row with another (67,555 rows for 111,244
  keys), and a kernel that issued one descriptor a ROW would save 1.5 ms
  of the 3.96 but needs the rows compacted first (a branch a slot costs
  more than the descriptor: the coalescing record above). (d) Two calls of
  the standing kernel on delta rows cost the same 4.3 ms and cannot serve
  a row two slots name; one kernel for both states reads the runs once and
  keeps ONE custom call in the program. (e) The 0.28 ms XLA spends writing
  the three blocks a slot (177 MB) would go with a kernel that derived the
  `run` block from the rows it already has in SMEM and spread a slot's
  value over its lanes itself: it changes the kernel's operands, a PR of
  its own. (f) The descriptors must be issued from straight-line code:
  from a loop, even of four passes of 64, a launch takes 2.3 ms more (the
  vector work and the next descriptors' addresses no longer overlap the
  issue). Unrolled in Python the kernel is traced once a slot and a
  program took 1.04-1.28 s to lower (PR 41); `fori_loop(unroll=True)`
  traces one slot and unrolls as it lowers: the same bits, the same ms.
  (g) What the module itself costs: importing it runs
  `jax.experimental.pallas`, 0.9-1.3 s of module code; PR 41 imported it
  at the top of a table file the package imports and every process paid
  (the benchmark's eight remote workers: `emb128.remote-workers`
  `setup_s` +1.15 s by ISSUE 42's reading of PR 41's lines; parity
  again in PR 42's pairs, 27.82 -> 27.95).
* the step worked out in VMEM, `add_at_lanes` under a caller's rule (PR 49,
  2026-10-03, one v5e chip, `TPU v5 lite`; the shapes of the record above:
  111,118 Zipf keys in 67,539 rows, 114,696 live slots of a 131,072 bucket,
  897 grid steps, `z` and `n` of 882,775,040 float32; the table's whole
  program, ms a launch, 30 launches back to back after a warm one, wall
  clock over the count, three rounds in one chip call, the order reversed
  in the second; seconds to `.lower()` on the chip's host, a fresh `jit`
  each round; every variant's first launch held to XLA's gathers, the same
  rule and XLA's scatters on the rows it names: `n` AND `z` equal in every
  bit of 8.6M entries, so Mosaic's float32 root and quotient round as
  XLA's do on this chip; my chip runs, PR 49).
    (i) the standing program: XLA gathers `z` and `n` as rows of 128, steps
        a slot, writes a `run` block and two delta blocks of (slots, 128)
        (118 MB of temporaries compiled, not 177: one block is folded);
        the kernel merges and adds    5.654 / 5.657 / 5.668   0.33 / 0.26 / 0.27 s
    (ii) the rule in the kernel on the blocks it read; XLA still writes the
        `run` block and ONE gradient block (59 MB)
                                      4.625 / 4.624 / 4.617   0.26 / 0.26 / 0.25 s
    (iii) keys and gradient handed lane-dense, (slots / 128, 128) int32 (1 MB
        of temporaries); the pipeline brings an (8, 128) tile every eighth
        step, the kernel takes its group's row by a dynamic sublane index,
        broadcasts it over 128 sublanes and transposes: slot k's key and
        bits on every lane of sublane k; `run` is `key >> 7`, a slot's lane
        `key & 127` against an iota
                                      4.299 / 4.291 / 4.291   0.27 / 0.27 / 0.25 s
    (iii) and the rule a chunk of 32 slots at a time, each chunk's 64
        write-backs issued before the next chunk is stepped (kept)
                                      4.174 / 4.165 / 4.171   0.29 / 0.33 / 0.29 s
      the same in chunks of 16        4.172 / 4.169 / 4.174   0.34 / 0.36 / 0.34 s
      the same in chunks of 64 (a second call, two rounds; (i) 5.660 /
        5.662 and the kept 4.168 / 4.169 beside it)   4.318 / 4.320   0.28 / 0.31 s
    (ii) in chunks of 32 / of 16      4.525-4.530 / 4.529-4.536
    (iii) with two additions for a rule (wrong; the price of the
        arithmetic), one round        4.004 (and (ii) so: 4.314)
  What Mosaic said (compiled here for the described v5e first, libtpu
  0.0.34, then on the chip): nothing against any form. `jnp.sign`, `abs`,
  `maximum`, `sqrt` and `/` of float32 `(128, 128)` and `(32, 128)` values
  lower as written; a `(1, 128)` row read at `pl.ds(step % 8, 1)` of an
  `(8, 128)` int32 block, `broadcast_to` `(128, 128)` and `.T` lower (a
  32-bit square transpose); the last `(8, 128)` block of a 897-row operand
  hangs over its end and is taken (its rows past the end are never
  indexed). What it says. (a) **XLA's gathers, selects and step were 1.03
  ms** ((i) - (ii)) and the blocks 0.33 more ((ii) - (iii)): 118 MB written
  and read again cost what ISSUE 49 read from PR 42's breakdown (0.28). (b)
  **The rule costs 0.29 ms where it sits between the read wait and the
  first write-back** (4.291 - 4.004: 0.32 us a grid step for three roots,
  two quotients and a dozen more operations on two blocks of 16 vregs) and
  0.17 in chunks: the LLO scheduler does run the vector unit under the
  scalar core's descriptor issue when the write-backs of the slots already
  stepped stand between the chunks; 16 slots hide no more than 32 and
  lower 0.05 s slower (the rule is traced once a chunk), and 64 read
  0.03 ms OVER the rule in one piece: 32 it is, `STEP_SLOTS`. (c) The two
  transposes a step are under the reads' landing: (iii) without a rule,
  4.004 ms for the whole program, is the sort (0.14) and 459,264
  descriptors at 8.4 ns. What is left is the descriptors: (a) and (b) of
  ROADMAP Queue 1 item 2.
* rows, not slots: `add_at_lanes` walks the keys' distinct rows (PR 51,
  2026-10-04, one v5e chip, `TPU v5 lite`; the cell's own generator at
  another seed: 111,312 Zipf keys in 67,245 rows, 114,696 live slots of a
  131,072 bucket, `z` and `n` of 882,775,040 float32; the table's whole
  program, ms a launch, 30 launches back to back after a warm one, wall
  clock over the count, three rounds in one chip call, the order reversed
  in the second; seconds to `.lower()` on the chip's host, a fresh `jit`;
  every variant's first launch held to XLA's gathers, the same rule and
  XLA's scatters on the rows it names, as a compact state of 8.6M
  entries: 0 entries of `z` and 0 of `n` differ in any bit, every variant;
  my chip runs, PR 51, call 1. `all-distinct`: the same count of keys, each
  in a row of its own, 111,312 rows).
    the standing program (PR 49's: a read and a write-back a SLOT and a
      state, the slots of a row merged by `_run_and`)
                                    4.158 / 4.162 / 4.154   (0.29-0.33 s)
      on all-distinct rows          4.141 / 4.141 / 4.139
    rows compacted by a second sort, the fold's planes bfloat16 with
      float32 sums, the slots' grid with dead steps under ONE `pl.when`,
      the first chunk of a step folded in line with the reads' issue and
      the rest in a loop       (A)  2.761 / 2.761 / 2.763   0.32 s
      on all-distinct rows          4.201 / 4.201 / 4.204
    (A) with the row list by a scatter at each slot's row index, sorted
      indices                       3.328 / 3.327 / 3.328   0.31 s
    (A) with the planes int8 and int32 sums (bytes less 128, put back
      under the count plane)        2.687 / 2.693 / 2.686   0.32 s
    (A) with the grid sized from `U` on the device and no `pl.when`
                                    2.768 / 2.769 / 2.768   0.38 s
      on all-distinct rows          4.220 / 4.220 / 4.221
    (A) with every chunk in the loop, the accumulator zeroed first
                                    2.840 / 2.840 / 2.840   0.32 s
      on all-distinct rows          4.148 / 4.146 / 4.149
    (A) with two chunks in line (the second masked where a step has one)
      and a loop for the rest       2.798 / 2.803 / 2.799   0.38 s
    calls 3 and 4, the kernel as kept: (A) with int8 planes of the SIGNED
      bytes (two shifts a plane; `& 255` puts them back), the fold's
      sublane iota and row index built INSIDE the step's `pl.when`
                                    2.682 / 2.690 / 2.681   0.34 s
      on all-distinct rows          4.139 / 4.138 / 4.134
      (the standing program beside it 4.155 / 4.156 / 4.159 and 4.148 /
      4.144 / 4.143)
    the same with those two values hoisted over the `pl.when`
                                    2.754 / 2.756 / 2.751   0.37 s
      (so read the tree of call 2: 2.746 / 2.750 / 2.756; the five planes
      cast to int8 whole after the concatenate or piece by piece before
      it: 2.757 / 2.754 / 2.755, no difference)
  What Mosaic said (each form compiled here for the described v5e first,
  libtpu 0.0.34, then run): nothing against any of them. The one-hot
  product with the contraction on the LAST dimension of both sides
  (`dot_general(((1,), (1,)))`, the slots on lanes on both: no transpose
  is written) lowers in bfloat16 and in int8; `(640, 128)` int32 and
  float32 values cast to int8 / bfloat16 after a concatenate of five
  `(128, 128)` pieces lower; a `fori_loop` between two scalars read from
  SMEM lowers; a grid bound that is a traced scalar lowers and runs; the
  slots and the gradient's bits whole in VMEM (`(1024, 128)` int32 each)
  with a `(1, 128)` row read at a dynamic sublane lower.
  What it says. (a) **1.40 ms a launch gone, a third of the program**
  (predicted 0.7-1.2; 1.47 as kept): 526 live steps issue 269,312
  descriptors where 897 issued 459,264, 1.63 ms at 8.6 ns, and what was
  feared against it is small: the program at 2.76 ms is those descriptors
  (2.32), the first sort (0.14) and 0.30 for the second sort, the
  cumulative sum, the chunk ranges, the fold and the dead steps together
  (as kept, in the cell's traced run: the kernel 2.378, of it 0.05 not
  descriptors; the second sort 0.115; the rest of the compaction 0.01). The fold costs little
  because most of it stands in line with the reads' issue: a step has 2.7
  chunks on average, the first is in line and the scalar core issues 256
  descriptors (2.2 us) over it; only the chunks after it wait in a loop
  ((A) against every chunk in the loop: 0.08 ms for the first chunks of
  526 steps). Two in line lose 0.04: the masked second chunk is computed
  in every step and hides nothing more. (b) **The row list by a second
  sort, not a scatter**: XLA's scatter of 114,816 single values into a
  512 KB array costs 0.57 ms more than sorting them, sorted indices or
  not. (c) **int8 planes save 0.07 ms on bfloat16** (half the bytes
  through the casts and the matrix unit's int8 rate) and state their
  exactness without an argument about mantissas: kept. **Two `(128, 128)`
  int32 values defined outside the step's `pl.when` and used inside it
  cost 0.066 ms a launch** (0.12 us a live step: they cross the branch
  through memory); inside it they cost nothing. (d) **The dead
  steps cost nothing that can be read**: the grid sized from `U` is
  0.007 ms SLOWER than 371 dead steps under one `pl.when`, and lowers
  0.06 s slower; the static grid stays. (e) **Where walking rows saves
  nothing** (every key a row of its own) the program as kept is level with
  the standing one (0.009 ms UNDER it; (A) was 0.06 over): the compaction
  and the fold cost what `_run_and` cost, and no knob is made for it. (f) `.lower()` is what it
  was: still ONE unrolled group of 512 descriptors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows (= concurrent DMAs) per grid step: a power of two, because bucket
# sizes are powers of two >= the group and any other group would violate
# the batch-multiple contract and drop updates. See the optimization record
# above for the measured sweep (PR 34); a new sweep sets this name on a
# scratch copy. The table's minimum bucket, a shard's segment step and the
# launch records' `waits` follow it.
ROW_GROUP = 256


def interpret_for(platform: str) -> bool:
    """Whether the row kernels interpret on ``platform`` — the platform of
    the devices that hold the table, not the process's default backend."""
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise ValueError(
        f"pallas row kernels run compiled on tpu and interpreted on cpu; "
        f"the table lives on {platform!r}")


LANES = 128     # one lane tile
SUBLANES = 8    # rows of one (8, 128) tile of 32-bit values
# VMEM a grid step of the scatter-add holds: the delta block, which the
# pipeline double-buffers, and the scratch the rows are read into, each
# ROW_GROUP x lanes values. The budget keeps that under three quarters of
# the 16 MiB a v5e kernel may use by default: 4,096 float32 lanes at a
# group of 256 (compiled for the described v5e at 4,096 and at 5,120 lanes,
# plain and counted; 8,192 runs out of VMEM in the compiler; PR 34). A
# wider table takes XLA's scatter (`fits_vmem` is part of the table's
# gate), it does not fail in the compiler.
VMEM_BUDGET_BYTES = 12 << 20


def fits_vmem(lanes: int, itemsize: int) -> bool:
    return 3 * ROW_GROUP * lanes * itemsize <= VMEM_BUDGET_BYTES


def lane_tiles(table) -> int:
    if table.shape[1] % LANES:
        raise ValueError(
            f"pallas row kernels take tables of whole {LANES}-lane tiles; "
            f"this one has {table.shape[1]} columns")
    return table.shape[1] // LANES


def _tile_view(table: jax.Array) -> jax.Array:
    """``(rows, T x 128)`` as its (8, 128) tiles lie in HBM:
    ``(rows / 8, T, 8, 128)``. XLA makes this a bitcast on the TPU (the
    minor two dimensions are one tile), so the kernel works on the table's
    own buffer. A table of one tile is already row-major and is returned as
    it is."""
    tiles = lane_tiles(table)
    if tiles == 1:
        return table
    if table.shape[0] % SUBLANES:
        raise ValueError(
            f"a table of {tiles} lane tiles needs a multiple of {SUBLANES} "
            f"rows, got {table.shape[0]}")
    return table.reshape(table.shape[0] // SUBLANES, SUBLANES, tiles,
                         LANES).transpose(0, 2, 1, 3)


def _row_view(view: jax.Array) -> jax.Array:
    """The inverse of :func:`_tile_view`."""
    if view.ndim == 2:
        return view
    blocks, tiles = view.shape[:2]
    return view.transpose(0, 2, 1, 3).reshape(blocks * SUBLANES,
                                              tiles * LANES)


def _row_of(table_ref, rid):
    """The HBM ref of table row ``rid``: 512 contiguous bytes of a one-tile
    table, else the row's ``T`` runs of 512 bytes in the tile view, for one
    strided descriptor."""
    if len(table_ref.shape) == 2:
        return table_ref.at[rid]
    # ids are never negative: a shift and a mask, where // and % would
    # spend a dozen scalar instructions a descriptor on the sign
    return table_ref.at[rid >> (SUBLANES.bit_length() - 1), :,
                        pl.ds(rid & (SUBLANES - 1), 1), :]


def _slot_of(block_ref, k):
    """Slot ``k`` of a VMEM row block: ``(ROW_GROUP, 128)`` for one tile,
    ``(T, ROW_GROUP, 128)`` (lane tile major, so that each tile of the
    block is a plain 2-D array to the vector unit) for more."""
    if len(block_ref.shape) == 2:
        return block_ref.at[k]
    return block_ref.at[:, pl.ds(k, 1), :]


def _block_shape(tiles: int):
    return (ROW_GROUP, LANES) if tiles == 1 else (tiles, ROW_GROUP, LANES)


def _gather_kernel(ids_ref, table_ref, out_ref, sem):
    g = pl.program_id(0)
    base = g * ROW_GROUP
    for k in range(ROW_GROUP):
        rid = ids_ref[base + k]
        pltpu.make_async_copy(_row_of(table_ref, rid), _slot_of(out_ref, k),
                              sem).start()
    # every row copy signals the one semaphore, which counts bytes: one
    # wait the size of the whole block (see `_scatter_add_kernel`)
    pltpu.make_async_copy(out_ref, out_ref, sem).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_call(table, ids, interpret):
    batch = ids.shape[0]
    tiles = lane_tiles(table)
    if tiles == 1:
        out_shape, out_map = (batch, LANES), lambda g, ids: (g, 0)
    else:  # lane tile major; put back in row order below
        out_shape, out_map = (tiles, batch, LANES), lambda g, ids: (0, g, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch // ROW_GROUP,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(_block_shape(tiles), out_map,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        _gather_kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, table.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(ids, _tile_view(table))
    if tiles == 1:
        return out
    return out.transpose(1, 0, 2).reshape(batch, tiles * LANES)


def gather_rows(table: jax.Array, ids: jax.Array, *,
                interpret: bool) -> jax.Array:
    """``table[ids]`` via overlapped row DMAs. ids: int32, len % ROW_GROUP == 0."""
    if ids.shape[0] % ROW_GROUP:
        raise ValueError(
            f"gather_rows: batch {ids.shape[0]} not a multiple of {ROW_GROUP}")
    return _gather_call(table, ids, interpret)


def _scatter_add_kernel(*refs, rows, sign, counted):
    if counted == "operand":
        # a shard's launch: the slots from ``count`` on issue no descriptor
        ids_ref, count_ref, delta_ref, table_in_ref, table_ref, scratch, \
            sems = refs
    else:
        ids_ref, delta_ref, table_in_ref, table_ref, scratch, sems = refs
    del table_in_ref  # aliased with table_ref; all access goes through out
    g = pl.program_id(0)
    base = g * ROW_GROUP
    # a DMA semaphore counts bytes: every read of a group signals the one,
    # every write-back the other, and one wait takes a whole group's off
    read_sem, write_sem = sems.at[0], sems.at[1]

    def read_dma(k):
        rid = ids_ref[base + k]
        return pltpu.make_async_copy(_row_of(table_ref, rid),
                                     _slot_of(scratch, k), read_sem)

    def write_dma(k):
        rid = ids_ref[base + k]
        return pltpu.make_async_copy(_slot_of(scratch, k),
                                     _row_of(table_ref, rid), write_sem)

    def add_delta():
        delta = delta_ref[:, :]
        if rows % ROW_GROUP:
            # the last group hangs over the delta's end: that part of the
            # block is unspecified (it may hold NaN), so select, never
            # multiply
            row = base + jax.lax.broadcasted_iota(jnp.int32, delta.shape, 0)
            delta = jnp.where(row < rows, delta, 0.0)
        if sign != 1.0:
            delta = sign * delta
        # the delta is as wide as the caller's columns: the lanes past them
        # (the last tile's padding) are written back as they were read
        width = delta.shape[1]
        if len(scratch.shape) == 2:
            scratch[:, :width] = scratch[:, :width] + delta
        else:
            for t in range(pl.cdiv(width, LANES)):
                lo, w = t * LANES, min(LANES, width - t * LANES)
                scratch[t, :, :w] = scratch[t, :, :w] + delta[:, lo:lo + w]

    def walk(live):
        """Read, add and write back the group's first ``live`` slots: the
        whole group unrolled and awaited once a direction where ``live`` is
        the static group size; a loop and a wait a slot where it is a count
        known on the chip (a shard's last group, once a launch; the slots
        past it hold whatever the scratch held and are not written)."""
        whole = isinstance(live, int)

        def each(step):
            if whole:
                for k in range(live):
                    step(k)
            else:
                jax.lax.fori_loop(0, live, lambda k, _: step(k), None)

        def land(row_dma, sem):
            if whole:
                # a descriptor the size of the group's rows, to wait on and
                # never to start: a wait reads the size and the semaphore
                pltpu.make_async_copy(scratch, scratch, sem).wait()
            else:
                each(lambda k: row_dma(k).wait())

        each(lambda k: read_dma(k).start())
        land(read_dma, read_sem)
        add_delta()
        each(lambda k: write_dma(k).start())
        # write-backs must land before the next grid step may read these
        # rows (live ids are unique per call, but a later *call* may touch
        # them)
        land(write_dma, write_sem)

    if not counted:
        walk(ROW_GROUP)
        return
    # "tail": a delta longer than its ids, the count in the last id slot
    count = (count_ref[0] if counted == "operand"
             else ids_ref[ids_ref.shape[0] - 1])
    live = count - base
    pl.when(live >= ROW_GROUP)(lambda: walk(ROW_GROUP))
    pl.when(jnp.logical_and(live > 0, live < ROW_GROUP))(lambda: walk(live))


def launched_slots(rows: int, group: int = 0) -> int:
    """Id slots a scatter-add of ``rows`` delta rows reads, adds and writes:
    whole groups of ``group`` slots, ``ROW_GROUP`` unless the kernel walks
    groups of its own (``add_at_lanes``)."""
    group = group or ROW_GROUP
    return pl.cdiv(rows, group) * group


def launch_waits(rows: int, group: int = 0) -> int:
    """Semaphore waits that launch issues: one for a group's reads and one
    for its write-backs (its descriptors are two a slot)."""
    return 2 * pl.cdiv(rows, group or ROW_GROUP)


def _scatter_add(table, ids, deltas, interpret, sign, count=None,
                 tail_count=False):
    """The scatter-add's ``pallas_call``, traceable: ``count`` (int32, one
    element) is the number of leading id slots that are live, and with
    ``tail_count`` the ids' own last slot holds that number (the operands
    are the plain call's: no second upload, and the same custom call to a
    trace); without either every slot of the delta's row groups is."""
    rows, width = deltas.shape
    tiles = lane_tiles(table)
    deltas = deltas.astype(table.dtype)
    view = _tile_view(table)
    counted = ("operand" if count is not None
               else "tail" if tail_count else "")
    prefetch = (ids, count) if count is not None else (ids,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        # the delta sizes the grid: id slots past its last group (the tail
        # of a caller's bucket) are never read
        grid=(pl.cdiv(rows, ROW_GROUP),),
        in_specs=[
            # as wide as the delta itself (a block may span a whole
            # dimension whatever its size): no pad program in front
            pl.BlockSpec((ROW_GROUP, width), lambda g, *_: (g, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM(_block_shape(tiles), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),  # the reads', the write-backs'
        ],
    )
    return _row_view(pl.pallas_call(
        functools.partial(_scatter_add_kernel, rows=rows, sign=sign,
                          counted=counted),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        grid_spec=grid_spec,
        # operand order: the scalar prefetch, deltas, table → alias table
        input_output_aliases={len(prefetch) + 1: 0},
        interpret=interpret,
    )(*prefetch, deltas, view))


@functools.partial(jax.jit,
                   static_argnames=("interpret", "sign", "tail_count"),
                   donate_argnums=(0,))
def _scatter_add_call(table, ids, deltas, interpret, sign=1.0,
                      tail_count=False):
    return _scatter_add(table, ids, deltas, interpret, sign,
                        tail_count=tail_count)


def scatter_add_rows(table: jax.Array, ids: jax.Array, deltas: jax.Array,
                     *, interpret: bool, sign: float = 1.0,
                     tail_count: bool = False) -> jax.Array:
    """In-place ``table.at[ids[:n]].add(sign * deltas)`` for the ``n`` rows
    of ``deltas`` and unique live ids; the input table buffer is donated.
    ``ids`` may be longer than ``deltas``: the slots of the last row group
    past ``n`` are read and written back unchanged, later ones not at all.

    ``tail_count``: the delta is longer than its ids. ``ids[-1]`` is the
    number of leading slots that name rows; the delta's rows from there on
    are not applied and their slots issue no descriptor, whatever either
    holds. One program serves every count under a delta's shape."""
    if ids.shape[0] % ROW_GROUP:
        raise ValueError(
            f"scatter_add_rows: batch {ids.shape[0]} not a multiple of {ROW_GROUP}")
    if deltas.shape[0] > ids.shape[0]:
        raise ValueError(
            f"scatter_add_rows: {deltas.shape[0]} delta rows for "
            f"{ids.shape[0]} id slots")
    if deltas.shape[1] > table.shape[1]:
        raise ValueError(
            f"scatter_add_rows: deltas of {deltas.shape[1]} columns for a "
            f"table of {table.shape[1]}")
    if not deltas.shape[0]:
        return table
    return _scatter_add_call(table, ids, deltas, interpret, sign, tail_count)


# ids a kernel's scalar prefetch may hold: 512 KB of the chip's 1 MB of SMEM
# (an op's bucket is a power of two; the next one would take all of it)
PREFETCH_SLOTS = 131_072
# rows a grid step of `add_at_lanes` walks: its own group, because its
# descriptors are four a row (a grid step's fixed cost is a twentieth of
# their issue time at 128 as at 256) and a program lowers in the time its
# unrolled descriptors take (the optimization record, PR 42, has both
# sweeps). It is the lanes of a row: the slots come to the kernel 128 to a
# lane-dense row, a chunk, and a chunk is folded onto a group's rows by one
# square matrix product (PR 51)
LANE_GROUP = LANES
# rows whose rule is worked out between two batches of write-backs: the
# vector unit steps the next chunk while the scalar core issues the last
# one's descriptors (whole sublane tiles; the record, PR 49, prices 16, 32,
# 64 and the whole group)
STEP_SLOTS = 32
# a delta's 32 bits travel through the matrix unit as its four bytes, signed:
# int8 planes, int32 sums
_PLANES, _PLANE_BITS = 4, 8
_LANE_BITS = LANES.bit_length() - 1


def _add_brought(olds, brought):
    """The step of a caller without a rule: every state takes its delta."""
    return [old + delta for old, delta in zip(olds, brought)]


def _lane_add_kernel(rows_ref, walked_ref, chunks_ref, slots_ref, *refs,
                     states, brings, step, interpret):
    deltas, refs = refs[:brings], refs[brings:]
    # the inputs are aliased with the outputs; all access goes through out
    tables, blocks = refs[states:2 * states], refs[2 * states:3 * states]
    folded, sems = refs[3 * states], refs[3 * states + 1]
    group = pl.program_id(0)
    base = group * LANE_GROUP
    walked = walked_ref[0]
    read_sem, write_sem = sems.at[0], sems.at[1]

    def each(row, lo=0, count=LANE_GROUP):
        # compiled, the loop is unrolled as it is lowered (from a rolled
        # one, 64 slots a pass, the launch takes 6.67 ms for 4.34: the
        # optimization record, PR 41); interpreted it stays rolled (XLA's
        # CPU compiler takes half a minute a shape over 1,024 copies)
        jax.lax.fori_loop(lo, lo + count, lambda k, _: row(k), None,
                          unroll=not interpret)

    def read(k):
        rid = rows_ref[base + k]
        for table, block in zip(tables, blocks):
            pltpu.make_async_copy(table.at[rid], block.at[k],
                                  read_sem).start()

    def write(k):
        rid = rows_ref[base + k]
        for table, block in zip(tables, blocks):
            pltpu.make_async_copy(block.at[k], table.at[rid],
                                  write_sem).start()

    def walk():
        each(read)
        # under the landing reads, the fold. Built here, inside the step's
        # one branch: hoisted over the `pl.when` these two cost 0.07 ms a
        # launch (the record, PR 51)
        sublane = jax.lax.broadcasted_iota(jnp.int32, (LANE_GROUP, LANES), 0)
        # the sublanes past the last row walked are more copies of it
        # (`rows_ref` names it there): they take what its slots bring too
        row = jnp.minimum(base + sublane, walked - 1)

        def fold(chunk):
            """What a chunk's 128 slots bring to this group's rows:
            ``(planes x LANE_GROUP, 128)`` int32, byte ``p`` of delta ``i``
            (signed) at the rows from ``(4 i + p) x LANE_GROUP``, the count
            of stepping slots that name a lane last. A slot is a lane of
            the chunk's row here: its row of the group is a one-hot over
            the sublanes on one side of the product, its lane of that row
            a one-hot on the other, and the matrix unit multiplies a byte
            by one and adds zeros, in integers: exact, whatever bits the
            delta holds."""
            slots = slots_ref[pl.ds(chunk, 1), :]
            # a slot that steps nothing holds -1: no row's
            lands = jnp.broadcast_to(slots >> _LANE_BITS, row.shape) == row
            names = jnp.broadcast_to(slots & (LANES - 1),
                                     row.shape) == sublane
            planes = []
            for delta in deltas:
                bits = delta[pl.ds(chunk, 1), :]
                for p in range(_PLANES):
                    # the byte, its sign spread (an arithmetic shift)
                    plane = bits << (32 - _PLANE_BITS * (p + 1)) >> (
                        32 - _PLANE_BITS)
                    planes.append(jnp.where(
                        lands, jnp.broadcast_to(plane, row.shape),
                        0).astype(jnp.int8))
            planes.append(jnp.where(lands, 1, 0).astype(jnp.int8))
            return jax.lax.dot_general(
                jnp.concatenate(planes, axis=0),
                jnp.where(names, 1, 0).astype(jnp.int8),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)

        # the slots of the group's rows are the sorted slots of a run of
        # chunks, at least one. The first in line with the reads' issue,
        # the rest in a loop of vector work alone
        groups = chunks_ref.shape[0] // 2
        first, end = chunks_ref[group], chunks_ref[groups + group]
        folded[...] = fold(first)

        def more(chunk, _):
            folded[...] += fold(chunk)

        jax.lax.fori_loop(first + 1, end, more, None)
        # every (row, lane) is named by one stepping slot at most, so the
        # planes added: each holds that slot's byte, or nothing
        summed = folded[...]
        stepped = summed[_PLANES * brings * LANE_GROUP:, :] > 0
        brought = []
        for i in range(brings):
            bits = 0
            for p in range(_PLANES):
                at = (_PLANES * i + p) * LANE_GROUP
                bits |= ((summed[at:at + LANE_GROUP, :]
                          & ((1 << _PLANE_BITS) - 1)) << (_PLANE_BITS * p))
            brought.append(jax.lax.bitcast_convert_type(bits,
                                                        blocks[0].dtype))
        for block in blocks:  # a semaphore counts bytes: a block's a wait
            pltpu.make_async_copy(block, block, read_sem).wait()
        # the rule a chunk of rows at a time, each chunk's write-backs
        # issued before the next chunk is stepped: the vector unit works
        # under the scalar core's issue of the descriptors
        for lo in range(0, LANE_GROUP, STEP_SLOTS):
            part = slice(lo, lo + STEP_SLOTS)
            olds = [block[part, :] for block in blocks]
            news = step(olds, [b[part, :] for b in brought])
            for block, old, new in zip(blocks, olds, news):
                # a lane nobody names is written back as read, whatever the
                # rule made of it: a select, because the vector unit flushes
                # a denormal that it adds anything to
                block[part, :] = jnp.where(stepped[part, :], new, old)
            each(write, lo, STEP_SLOTS)
        # the next grid step reads other rows into these blocks
        for block in blocks:
            pltpu.make_async_copy(block, block, write_sem).wait()

    # the grid is the slots': a step past the rows walked does nothing
    pl.when(base < walked)(walk)


def _rows_walked(keys, steps):
    """The walk of sorted ``keys`` (whole groups of slots), made on the
    device: the distinct rows they live in, ascending, then the last one
    again for every slot left (``rows``: the kernel's descriptors' scalar
    prefetch); their count (``walked``, one element); for each group of
    ``LANE_GROUP`` of those rows the chunks of 128 slots that hold its
    keys, first and end (``chunks``: the firsts, then the ends; a chunk may
    serve two groups); and the slots themselves, 128 to a lane-dense row:
    ``index of its row << 7 | its lane``, or -1 for a slot that steps
    nothing."""
    rows = keys >> _LANE_BITS
    first = jnp.concatenate([jnp.ones(1, bool), rows[1:] != rows[:-1]])
    index = jnp.cumsum(first.astype(jnp.int32)) - 1
    # a second sort, not a scatter at `index` (the record, PR 51): the
    # firsts ascend already and every other slot sorts behind them
    distinct = jnp.minimum(
        jnp.sort(jnp.where(first, rows, jnp.iinfo(jnp.int32).max)), rows[-1])
    slots = jnp.where(steps, index << _LANE_BITS | keys & (LANES - 1), -1)
    by_chunk = index.reshape(-1, LANES)
    groups = keys.shape[0] // LANE_GROUP
    base = LANE_GROUP * jnp.arange(groups, dtype=jnp.int32)[:, None]
    # the indexes ascend: the chunks wholly before a group, and those that
    # begin before its end
    chunks = jnp.concatenate([
        jnp.sum(by_chunk[None, :, -1] < base, axis=1, dtype=jnp.int32),
        jnp.sum(by_chunk[None, :, 0] < base + LANE_GROUP, axis=1,
                dtype=jnp.int32)])
    return distinct, index[-1:] + 1, chunks, slots.reshape(-1, LANES)


def add_at_lanes(states, keys, deltas, steps, *, step=None,
                 interpret: bool):
    """``states = step(states at keys, deltas)`` at the slots ``steps``
    marks, in place, by row descriptors, and the count of rows walked
    (int32, on the device): traceable, for a caller's jitted program that
    donates the states. A state is a lane-dense float32 ``(n x 128,)``
    array, read here as rows of 128 (a bitcast); ``keys`` are int32 in
    ``[0, n x 128)``, ASCENDING, at least one and at most
    ``PREFETCH_SLOTS`` with the slots that fill their last group (those
    repeat the last key and step nothing); a delta is a float32 a slot,
    ``steps`` a bool a slot. ``step(olds, brought) -> news`` is the
    caller's rule, plain ``jax.numpy``, elementwise: ``olds`` the states'
    values and ``brought`` the deltas' (as many as the caller gave, not
    one a state), all of one shape, and it returns a new value a state;
    without one every state takes its own delta, ``state[key] += delta``.

    The kernel walks ROWS, not slots (PR 51). In front of it, in the
    caller's program and with no word to the host, the keys' distinct rows
    are compacted (``_rows_walked``): ``U`` of them, a number the program
    reads from its input and returns. A grid step reads ``LANE_GROUP`` of
    those rows of every state (one descriptor a row and a state), folds
    onto them what the slots of their keys bring, applies the rule to the
    ``(LANE_GROUP, 128)`` blocks where the rows landed, in VMEM, and writes
    them back; ``ceil(U / LANE_GROUP)`` steps do that, the rest of the
    slots' grid nothing. No row is named by two steps. The caller's
    program neither gathers a state nor spreads anything over the lanes of
    a row.

    * **The slots of a step's rows** are a run of the sorted slots, 128 to
      a lane-dense chunk. A chunk is folded onto the rows by the matrix
      unit: slot ``s`` to the sublane of its row and the lane of its key,
      two one-hots around the delta's four bytes, int8 planes summed in
      int32, which the product carries exactly. The bits arrive as they were given: a
      NaN (its payload too), an infinity, ``-0.0`` and a denormal reach
      their own key's lane and no other.
    * **A slot that steps nothing** (a pad, wherever it aims: the scratch
      key may share its row with live keys) brings nothing. Its row is
      walked like any other and written back as its other slots make it,
      or as read.
    * **A key named by several slots** (they are adjacent) is stepped once,
      by what its FIRST slot brings; the others' deltas are not read,
      whichever chunks they lie in.
    * **A lane no stepping slot names** is written back as read, by a
      select: its bits stand, a denormal's too. The rule IS computed on it,
      from its value and a delta of 0.0, and the result dropped: a rule may
      make of that what it likes, it cannot trap.
    * **The sublanes of the last step past ``U``** name the last distinct
      row again and take all that its slots bring: every copy writes the
      same bytes, whichever lands last.
    A stepped lane takes the rule's float32 operations on the numbers it
    would take on gathered values (without a rule, the one addition
    ``state.at[key].add(delta)`` makes)."""
    slots = launched_slots(keys.shape[0], LANE_GROUP)
    if not 0 < slots <= PREFETCH_SLOTS:
        raise ValueError(
            f"add_at_lanes: {keys.shape[0]} keys; 1 to {PREFETCH_SLOTS} "
            f"in whole groups of {LANE_GROUP} are served")
    if step is None:
        if len(deltas) != len(states):
            raise ValueError(
                f"add_at_lanes: {len(deltas)} deltas for {len(states)} "
                f"states and no rule")
        step = _add_brought
    # a key several slots name steps at its first: the others bring nothing
    steps = steps & jnp.concatenate(
        [jnp.ones(1, bool), keys[1:] != keys[:-1]])
    tail = slots - keys.shape[0]
    if tail:  # whole groups: more slots of the last key, stepping nothing
        keys = jnp.concatenate([keys, jnp.broadcast_to(keys[-1:], (tail,))])
        steps = jnp.concatenate([steps, jnp.zeros(tail, bool)])
        deltas = [jnp.concatenate([d, jnp.zeros(tail, d.dtype)])
                  for d in deltas]
    count = len(states)
    rows, walked, chunks, lane_slots = _rows_walked(keys, steps)
    views = [s.reshape(-1, LANES) for s in states]
    # the slots and the deltas' bits whole in VMEM (512 KB each at the
    # largest bucket): a step's chunks are wherever its rows' keys sorted to
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots // LANE_GROUP,),
        in_specs=[whole] * (1 + len(deltas))
        + [pl.BlockSpec(memory_space=pl.ANY)] * count,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * count,
        scratch_shapes=[pltpu.VMEM((LANE_GROUP, LANES), v.dtype)
                        for v in views]
        + [pltpu.VMEM(((_PLANES * len(deltas) + 1) * LANE_GROUP, LANES),
                      jnp.int32),
           pltpu.SemaphoreType.DMA((2,))],  # the reads', the write-backs'
    )
    out = pl.pallas_call(
        functools.partial(_lane_add_kernel, states=count,
                          brings=len(deltas), step=step, interpret=interpret),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype) for v in views],
        grid_spec=grid_spec,
        # operand order: the rows, their count, the chunks, the slots, the
        # deltas, the states
        input_output_aliases={4 + len(deltas) + i: i for i in range(count)},
        interpret=interpret,
    )(rows, walked, chunks, lane_slots,
      *[jax.lax.bitcast_convert_type(d, jnp.int32).reshape(-1, LANES)
        for d in deltas], *views)
    return tuple(o.reshape(-1) for o in out), walked[0]
