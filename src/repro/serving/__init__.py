"""repro.serving — multi-position decode engine, the common parallel-
decoding protocol (slot adapters), and the multi-request scheduler."""
from repro.serving.algorithm import SlotAdapter
from repro.serving.diffusion import DiffusionSlotAdapter
from repro.serving.engine import DecodeEngine
from repro.serving.mtp import MTPSlotAdapter, init_mtp_heads, mtp_loss
from repro.serving.paged import (BlockAllocator, BlockManager, PagedKVConfig,
                                 PrefixCache)
from repro.serving.scheduler import (DEFAULT_SLO_CLASSES, AdmissionConfig,
                                     AdmissionRejected, Request, SLOClass,
                                     ServingLoop)
from repro.serving.speculative import SpeculativeSlotAdapter, ngram_draft

__all__ = ["AdmissionConfig", "AdmissionRejected", "BlockAllocator",
           "BlockManager", "DecodeEngine", "DEFAULT_SLO_CLASSES",
           "PagedKVConfig", "PrefixCache", "SLOClass", "SlotAdapter",
           "SpeculativeSlotAdapter", "DiffusionSlotAdapter",
           "MTPSlotAdapter", "Request", "ServingLoop", "init_mtp_heads",
           "mtp_loss", "ngram_draft"]
