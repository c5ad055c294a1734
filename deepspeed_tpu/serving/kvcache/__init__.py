"""Paged KV subsystem: page pool + allocator, shared-prefix dedup,
copy-on-write pages, durable session KV, and hierarchical HBM → host →
disk page tiering (docs/serving.md §Paged KV & prefix caching, §KV
tiering)."""
from deepspeed_tpu.serving.kvcache.pages import GARBAGE_PAGE, HybridKV, IndexedKV, LatentKV, PagedKVPool, PerHeadKV, WindowedKV
from deepspeed_tpu.serving.kvcache.prefix import PrefixEntry, PrefixIndex
from deepspeed_tpu.serving.kvcache.sessions import Session, SessionStore
from deepspeed_tpu.serving.kvcache.tiers import PageTierManager, TierEntry

__all__ = [
    "GARBAGE_PAGE",
    "HybridKV",
    "IndexedKV",
    "LatentKV",
    "PagedKVPool",
    "PerHeadKV",
    "PageTierManager",
    "PrefixEntry",
    "PrefixIndex",
    "Session",
    "SessionStore",
    "TierEntry",
    "WindowedKV",
]
