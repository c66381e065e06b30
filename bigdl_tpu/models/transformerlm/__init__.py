from bigdl_tpu.models.transformerlm.transformerlm import (
    PositionEmbedding, TransformerBlock, TransformerLM, lm_criterion,
)
from bigdl_tpu.models.transformerlm.decoder import (
    ConfigDecoder, WeightedTokenCriterion,
)
