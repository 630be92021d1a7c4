"""Tuned-key lint: every block knob the kernel/serving tier references
must exist in the packaged tuned tables (or be explicitly allowlisted).

The override registry (:mod:`apex_tpu.kernels.vmem`) is stringly typed:
``get_override("decode.blokc_k", ...)`` is not an error, it is a silent
fall-through to the untuned default — a typo'd key costs real tokens/s
on silicon and nothing ever flags it. This lint closes the loop: the
set of key literals referenced by ``apex_tpu/kernels/`` and
``apex_tpu/serving/`` source must be a subset of the union of keys
across ``apex_tpu/kernels/tuned/*.json`` plus the documented
``EXPLICITLY_DEFAULTED`` set, and the tables must not carry keys no
code consumes (a stale table row is a sweep that no longer tunes
anything).
"""

import glob
import json
import os
import re

import pytest

pytestmark = pytest.mark.serving

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, os.pardir, os.pardir))
TUNED_DIR = os.path.join(ROOT, "apex_tpu", "kernels", "tuned")
SCAN_DIRS = [os.path.join(ROOT, "apex_tpu", "kernels"),
             os.path.join(ROOT, "apex_tpu", "serving")]

# Keys a call site may reference without a packaged tuned value: add an
# entry here ONLY with a comment saying why the heuristic default is the
# intended production value.
EXPLICITLY_DEFAULTED: set = set()


def _table_keys():
    keys = set()
    files = glob.glob(os.path.join(TUNED_DIR, "*.json"))
    assert files, f"no tuned tables under {TUNED_DIR}"
    for path in files:
        with open(path) as f:
            keys |= set(json.load(f))
    return keys


def _referenced_keys(prefixes):
    """Quoted ``family.knob`` literals in the scanned sources, filtered
    to known tuned-key families so einsum specs / file names / metric
    names never false-positive."""
    pat = re.compile(r'["\']([a-z0-9_]+\.[a-z0-9_]+)["\']')
    refs = {}
    for d in SCAN_DIRS:
        for path in glob.glob(os.path.join(d, "**", "*.py"),
                              recursive=True):
            with open(path) as f:
                for key in pat.findall(f.read()):
                    if key.split(".", 1)[0] in prefixes:
                        refs.setdefault(key, []).append(
                            os.path.relpath(path, ROOT))
    return refs


def test_every_referenced_tuned_key_exists_in_the_tables():
    table = _table_keys()
    prefixes = {k.split(".", 1)[0] for k in table}
    refs = _referenced_keys(prefixes)
    assert refs, "lint found no tuned-key references at all — the " \
        "regex or scan dirs are broken, not the code"
    missing = {k: v for k, v in refs.items()
               if k not in table and k not in EXPLICITLY_DEFAULTED}
    assert not missing, (
        f"tuned keys referenced in code but absent from every table in "
        f"{TUNED_DIR} (typo, or add the key to the tables / "
        f"EXPLICITLY_DEFAULTED): {missing}")


def test_no_stale_table_keys():
    table = _table_keys()
    prefixes = {k.split(".", 1)[0] for k in table}
    refs = set(_referenced_keys(prefixes))
    stale = table - refs
    assert not stale, (
        f"tuned tables carry keys no kernel/serving code references "
        f"(dead sweep rows — delete them or wire a consumer): {stale}")


def test_chunk_prefill_keys_are_tuned():
    """The chunked-prefill kernel's knobs ship tuned values (the PR 4
    satellite): a fresh engine on v5e silicon must not fall back to
    emulator-era defaults for its hottest new program."""
    table = _table_keys()
    for key in ("decode.chunk_block_q", "decode.chunk_block_k"):
        assert key in table, f"{key} missing from the tuned tables"


def test_paged_kernel_keys_are_tuned():
    """The paged-pool satellite: the block-table kernels' knobs ship
    tuned values — ``decode.page_block_q`` (the paged prefill kernel's
    q block; the KV block is pinned to one pool page) and
    ``decode.page_len`` (the Engine's default page size — the pool's
    sharing/DMA granule). A fresh paged engine on v5e silicon must not
    fall back to emulator-era defaults for its two hottest programs."""
    table = _table_keys()
    for key in ("decode.page_block_q", "decode.page_len"):
        assert key in table, f"{key} missing from the tuned tables"
    refs = _referenced_keys({"decode"})
    for key in ("decode.page_block_q", "decode.page_len"):
        assert key in refs, f"{key} is in the tables but no code " \
            "consumes it (stale sweep row)"


def test_prefix_copy_sources_are_linted_and_carry_no_tuned_keys():
    """The PR 5 prefix-reuse satellite, tightened by the paged-pool
    refactor that RETIRED the copy: a prefix hit is host-side
    page sharing (no program at all) — so it owes the tables no
    key, and NO ``decode.copy_*`` row may remain (a stale row would be
    a dead sweep, caught here by name rather than only via the generic
    stale check). Also pins that the lint's scan covers the sources the
    retired path and its replacement live in, so any key a future copy
    or paging kernel DOES reference gets the existence/staleness
    treatment automatically."""
    table = _table_keys()
    stale_copy = {k for k in table if k.startswith("decode.copy_")}
    assert not stale_copy, (
        f"tuned tables carry decode.copy_* keys but the zero-copy "
        f"hit path consumes no tuned knobs: {stale_copy}")
    scanned = {os.path.relpath(p, ROOT)
               for d in SCAN_DIRS
               for p in glob.glob(os.path.join(d, "**", "*.py"),
                                  recursive=True)}
    assert os.path.join("apex_tpu", "serving",
                        "prefix_cache.py") in scanned
    assert os.path.join("apex_tpu", "serving", "engine.py") in scanned
    assert os.path.join("apex_tpu", "serving", "kv_cache.py") in scanned


def test_speculative_verify_owes_the_tables_no_keys():
    """The speculative-decoding satellite, in the copy-program pattern:
    the verify program is the chunk-append machinery at a different
    shape — its attention rides the EXISTING ``decode.chunk_block_*`` /
    ``decode.page_block_q`` knobs and the drafter is pure host python —
    so no ``decode.verify_*`` key may exist in the tables (a row no
    code consumes would be a dead sweep; if a dedicated verify kernel
    ever lands, its keys get the existence/staleness treatment
    automatically because the scan covers speculative.py and
    engine.py)."""
    table = _table_keys()
    stale_verify = {k for k in table if k.startswith("decode.verify_")
                    or k.startswith("decode.spec_")}
    assert not stale_verify, (
        f"tuned tables carry verify/spec keys but the verify program "
        f"reuses the chunk-attention knobs: {stale_verify}")
    scanned = {os.path.relpath(p, ROOT)
               for d in SCAN_DIRS
               for p in glob.glob(os.path.join(d, "**", "*.py"),
                                  recursive=True)}
    assert os.path.join("apex_tpu", "serving",
                        "speculative.py") in scanned


def test_quantized_kv_owes_the_tables_no_new_keys():
    """The quantized-KV satellite, in the copy/verify/sharding
    pattern: dequantization is FUSED into the existing attention
    kernels (a per-head scalar multiply on the logit and accumulator
    updates — no new grid, block shape or index map), so the int8 tier
    introduces NO new ``decode.*`` table key; its kernels reuse the
    block knobs already swept. Any ``decode.qkv_*`` / ``decode.kv_*``
    row (a quantized-qkv or quant-specific sweep that no code consumes)
    is a dead row named loudly here; if a dedicated quant kernel ever
    lands, its keys get the existence/staleness treatment automatically
    because the scan covers serving/kv_quant.py and the two attention
    kernel files."""
    table = _table_keys()
    stale_quant = {k for k in table
                   if k.startswith(("decode.qkv_", "decode.kv_"))}
    assert not stale_quant, (
        f"tuned tables carry quantized-KV keys but the int8 tier "
        f"reuses the existing attention block knobs: {stale_quant}")
    scanned = {os.path.relpath(p, ROOT)
               for d in SCAN_DIRS
               for p in glob.glob(os.path.join(d, "**", "*.py"),
                                  recursive=True)}
    assert os.path.join("apex_tpu", "serving", "kv_quant.py") in scanned
    assert os.path.join("apex_tpu", "kernels",
                        "decode_attention.py") in scanned
    assert os.path.join("apex_tpu", "kernels",
                        "prefill_attention.py") in scanned


def test_quantized_weights_owe_the_tables_no_new_keys():
    """The quantized-weights satellite, in the quantized-KV pattern:
    dequantization is FOLDED into the existing GEMMs' epilogues (a
    per-output-channel scale multiply on the accumulator — no new
    kernel, grid or block shape), so the int8 weight tier introduces NO
    new ``decode.*`` table key. Any ``decode.wq_*`` / ``decode.weight_*``
    row would be a dead sweep, named loudly here; and the lint's scan
    must cover weight_quant.py and the shared quant core so any key a
    future dedicated int8-GEMM kernel DOES reference gets the
    existence/staleness treatment automatically."""
    table = _table_keys()
    stale_wq = {k for k in table
                if k.startswith(("decode.wq_", "decode.weight_"))}
    assert not stale_wq, (
        f"tuned tables carry quantized-weight keys but the int8 tier "
        f"folds dequant into the existing GEMM epilogues: {stale_wq}")
    scanned = {os.path.relpath(p, ROOT)
               for d in SCAN_DIRS
               for p in glob.glob(os.path.join(d, "**", "*.py"),
                                  recursive=True)}
    assert os.path.join("apex_tpu", "serving",
                        "weight_quant.py") in scanned
    assert os.path.join("apex_tpu", "serving",
                        "quant_common.py") in scanned


def test_host_tier_owes_the_tables_no_new_keys():
    """The hierarchical-KV satellite, in the copy-program pattern: the
    host tier is pure data movement — swap-out is one fixed-shape
    page-block gather and swap-in one fixed-shape page-block scatter
    (no attention, no Pallas kernel, no grid; both shard_map over the
    pool's heads axis under a mesh with zero collectives) —
    so it introduces NO new ``decode.*`` tuned key; restored pages are
    read back through the EXISTING paged-attention knobs. Any
    ``decode.swap_*`` / ``decode.host_*`` row would be a dead sweep,
    named loudly here; and the lint's scan must cover host_tier.py so
    any key a future swap-DMA kernel DOES reference gets the
    existence/staleness treatment automatically."""
    table = _table_keys()
    stale_swap = {k for k in table
                  if k.startswith(("decode.swap_", "decode.host_"))}
    assert not stale_swap, (
        f"tuned tables carry host-tier keys but swap-in/out is pure "
        f"data movement over the existing programs: {stale_swap}")
    scanned = {os.path.relpath(p, ROOT)
               for d in SCAN_DIRS
               for p in glob.glob(os.path.join(d, "**", "*.py"),
                                  recursive=True)}
    assert os.path.join("apex_tpu", "serving",
                        "host_tier.py") in scanned


def test_lora_tier_owes_the_tables_no_new_keys():
    """The multi-tenant LoRA satellite, in the copy/verify/host-tier
    pattern: the adapter epilogue is two skinny GEMMs fused onto the
    EXISTING projection matmuls (``acc + (x @ A) @ B * alpha`` — rank
    is 4–64, far below any block-tiling threshold; no new grid, block
    shape or Pallas kernel) and the arena swap path is pure data
    movement (one ``.at[row].set`` per site), so the tier introduces
    NO new ``decode.*`` tuned key. Any ``decode.lora_*`` /
    ``decode.adapter_*`` row would be a dead sweep, named loudly here;
    and the lint's scan must cover serving/lora.py so any key a future
    dedicated grouped-LoRA kernel DOES reference gets the
    existence/staleness treatment automatically."""
    table = _table_keys()
    stale_lora = {k for k in table
                  if k.startswith(("decode.lora_", "decode.adapter_"))}
    assert not stale_lora, (
        f"tuned tables carry LoRA keys but the adapter epilogue rides "
        f"the existing projection GEMMs' knobs: {stale_lora}")
    scanned = {os.path.relpath(p, ROOT)
               for d in SCAN_DIRS
               for p in glob.glob(os.path.join(d, "**", "*.py"),
                                  recursive=True)}
    assert os.path.join("apex_tpu", "serving", "lora.py") in scanned


def test_sharded_serving_owes_the_tables_no_new_keys():
    """The tensor-parallel satellite, in the copy/verify pattern: the
    sharded programs run the EXISTING paged kernels over fewer heads
    per shard (the grid's heads dimension shrinks; no index map or
    block shape changes), so sharding introduces NO new ``decode.*``
    table key — the per-shard kernels reuse the block knobs already
    swept (same shapes per head, fewer heads). The decode.* table
    surface is pinned by name, so a future sharded-attention knob must
    land here AND in the tables deliberately; and the lint's scan must
    cover serving/sharding.py so any key it ever does reference gets
    the existence/staleness treatment automatically."""
    table = {k for k in _table_keys() if k.startswith("decode.")}
    assert table == {
        "decode.chunk_block_q", "decode.chunk_block_k",
        "decode.page_block_q", "decode.page_len",
        # the paged decode kernel's bytes in flight a buffer (PR 31):
        # per shard it sees the local heads' page and reads its pages a
        # step off that, so sharding needs no value of its own
        "decode.paged_step_bytes",
    }, (f"decode.* table surface changed: {sorted(table)} — if a "
        "sharded-attention knob landed, update this pin deliberately")
    stale_tp = {k for k in _table_keys()
                if k.startswith(("decode.tp_", "decode.shard_"))}
    assert not stale_tp, (
        f"tuned tables carry tensor-parallel keys but the sharded "
        f"kernels reuse the existing block knobs: {stale_tp}")
    scanned = {os.path.relpath(p, ROOT)
               for d in SCAN_DIRS
               for p in glob.glob(os.path.join(d, "**", "*.py"),
                                  recursive=True)}
    assert os.path.join("apex_tpu", "serving", "sharding.py") in scanned
