"""Physical memory substrate under the paged-KV runtime.

The paper's two-tier local/pool memory system, made physical. Before
this package the pool tier was bookkeeping: `KVPager.phys_tiers()`
priced every page against `core.tiers` bandwidth/latency while all
pages lived in one default-memory array. The substrate realizes the
split:

  device pool   — the engine's paged cache leaves ("k"/"v" and the int8
                  "k_sz"/"v_sz" scale arrays) stay authoritative in
                  device memory: every kernel keeps reading the same
                  arrays, so token streams are bit-identical with the
                  substrate on or off.
  host twin     — a page-major twin of the paged leaves
                  (`models.blocks.paged_pool_leaves`): one tile-aligned
                  row block per page (see `tier_substrate`), placed with
                  a `pinned_host` NamedSharding when the backend supports
                  it. Pages whose pager tier is POOL are mirrored here;
                  LOCAL and free pages are not.

TIER TRANSITIONS ARE RECONCILED, NOT HOOKED: once per decode step the
engine calls `TierSubstrate.drain(pager, caches)`, which diffs the
pager's live pool set (`KVPager.pool_page_ids()`) against the pages
currently host-resident and issues the difference as async transfer
streams —

  page_out  — newly pool-tiered pages (hot-tail eviction, cold-prefix
              demotion, static-policy spill, COW copies landing in the
              pool) gather from the device pool and land in the donated
              host twin, one device->host DMA per page, in one jitted
              program whose output sharding IS the twin's placement.
  page_in   — pool pages promoted back to LOCAL come out of the twin
              one host->device DMA per page, returned in the pool's own
              layout; the device pool already holds the payload, so the
              result is only held for completion tracking.
  drop      — pages freed while pool-resident (slot release, prefix
              trie reclaim) leave the twin with zero transfer bytes.

Within-step churn (a page evicted and promoted between two drains)
coalesces to its net placement change — the stream contract is
placement-accurate, not event-replaying. Page-id vectors are padded to
power-of-two lengths (repeating the last id: writing the same page
twice with identical data is a no-op) so the jitted transfer programs compile
O(log pool_size) times, not per distinct burst size.

Every stream appends a `TransferEvent` to the `SubstrateLedger`:
MEASURED bytes (leaf `nbytes` of the actual pool arrays, not the
closed-form kv-byte walk), completion tracked via `sync()`
(`block_until_ready` over the in-flight payloads — transfers are
issued without blocking the step). The accounting contract, tested in
`tests/test_tier_substrate.py`:

    pager.pool_bytes_used() == ledger.placement_bytes()

after every drain, in both modes.

MODES (`runtime.capability.resolve_substrate_mode`): "physical" places
the twin with `memory_kind="pinned_host"` and needs the backend's
host-input, host-output and internal-transfer probes (XLA:TPU); "emulated" runs the
identical program shapes with default-memory placement (XLA:CPU, this
CI) so the ledger, byte accounting and tests are the same everywhere;
"auto" picks physical when the backend can; "off" disables the
substrate (and `ServingEngine` also disables it when the cache has no
paged leaves — SSM-only stacks have no page-addressable KV).
"""

from repro.serving.substrate.ledger import SubstrateLedger, TransferEvent
from repro.serving.substrate.tier_substrate import TierSubstrate

__all__ = ["SubstrateLedger", "TierSubstrate", "TransferEvent"]
