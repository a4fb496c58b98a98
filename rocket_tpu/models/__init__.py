import time as _time

_IMPORT_T0 = _time.perf_counter_ns()  # start-up record: startup/import

from rocket_tpu.models import objectives
from rocket_tpu.models.layers import Embed, PDense, RMSNorm, apply_rope, rotary_embedding
from rocket_tpu.models.generate import (
    ContinuousBatcher,
    beam_search,
    beam_search_cached,
    beam_search_seq2seq,
    generate,
    generate_seq2seq,
    speculative_generate,
    speculative_generate_batched,
    speculative_sample,
    speculative_sample_batched,
)
from rocket_tpu.models.lenet import LeNet
from rocket_tpu.models.lora import freeze_non_lora, freeze_where, is_lora, lora_labels, merge_lora
from rocket_tpu.models.resnet import ResNet, resnet18, resnet50
from rocket_tpu.models.seq2seq import EncoderDecoder, Seq2SeqConfig
from rocket_tpu.models.transformer import TransformerConfig, TransformerLM
from rocket_tpu.models.vit import ViT, ViTConfig

__all__ = [
    "ContinuousBatcher",
    "Embed",
    "beam_search",
    "beam_search_cached",
    "beam_search_seq2seq",
    "generate",
    "generate_seq2seq",
    "speculative_generate",
    "speculative_generate_batched",
    "speculative_sample",
    "speculative_sample_batched",
    "EncoderDecoder",
    "LeNet",
    "PDense",
    "RMSNorm",
    "ResNet",
    "Seq2SeqConfig",
    "TransformerConfig",
    "TransformerLM",
    "ViT",
    "ViTConfig",
    "apply_rope",
    "freeze_non_lora",
    "is_lora",
    "freeze_where",
    "lora_labels",
    "merge_lora",
    "objectives",
    "resnet18",
    "resnet50",
    "rotary_embedding",
]

from rocket_tpu.observe.trace import get_startup as _get_startup  # noqa: E402

_get_startup().mark("startup/import", _IMPORT_T0, _time.perf_counter_ns(),
                    package=__name__)
