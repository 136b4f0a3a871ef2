"""OLMoE (top-k routed SwiGLU experts, MHA with QK-norm): the Qwen3
module's block, which routes where a layer holds ``moe``.

The program departs from the published model in two ways, which the
reference follows: its QK-norm is per head, where OLMoE's normalises
the whole projection, and it always renormalises the top-k gates, so a
file that states ``norm_topk_prob`` false is refused
(``qwen3.program_config``).
"""

from benchmarks.chip.models.qwen3 import counts, logits, program_config

__all__ = ["counts", "logits", "program_config"]
