"""The llama decoder is the qwen2 decoder without the q/k/v biases,
which reference/qwen2.py adds only where the configuration's
``model_type`` asks for them."""

from reference.qwen2 import (embed, forward, layer, logits,  # noqa: F401
                             make_ends, make_layer, weight_keys)
