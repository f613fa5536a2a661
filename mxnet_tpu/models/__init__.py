"""``mxnet_tpu.models`` — modern model blocks beyond the reference zoo.

The reference's model zoo stops at CNN-era vision models plus fused-RNN NLP
primitives; BASELINE.json's stretch config (Llama-3-8B long-context) needs a
transformer LM with TP/SP/CP shardings — that lives here.
"""
import time as _time

_IMPORT_T0 = _time.monotonic()    # mx.start.import, recorded below

from .bert import (BertConfig, BERTForPretrain, BERTModel, bert_base_config,
                   bert_tiny_config)
from .transformer import (TransformerLM, TransformerBlock, LlamaConfig,
                          LayerSpec, evabyte_6p5b_config,
                          keye_vl2_30b_a3b_config, laguna_s21_config,
                          llama3_8b_config, ouro_2p6b_config, tiny_config)
from .looped import LoopedLM, exit_log_probs, expected_exit_loss
from .evabyte import EvaByteLM, chunk_summaries, eva_attention
from .kv_cache import CacheSpec, CacheView, init_pools
from .. import profiler as _profiler

_profiler.record_build_span("mx.start.import", _IMPORT_T0,
                            module=__name__)
