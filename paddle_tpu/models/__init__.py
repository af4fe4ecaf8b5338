"""Model zoo — parity with the reference's benchmark/fluid/models and
book examples, plus the Llama flagship. The serving-only block-kind models
beside ``latent_moe`` (``hybrid_moe``, ``hybrid_ssm``, ``hybrid_delta``,
``looped``) are imported by whoever serves them, not here: nothing of
theirs runs at ``import paddle_tpu``."""
from . import mnist           # noqa: F401
from . import vgg             # noqa: F401
from . import resnet          # noqa: F401
from . import se_resnext      # noqa: F401
from . import stacked_dynamic_lstm  # noqa: F401
from . import machine_translation   # noqa: F401
from . import transformer     # noqa: F401
from . import llama           # noqa: F401
from . import latent_moe      # noqa: F401
from . import word2vec        # noqa: F401
from . import recommender     # noqa: F401
from . import ctr             # noqa: F401
from . import faster_rcnn     # noqa: F401
from . import fit_a_line      # noqa: F401
from . import ocr_recognition  # noqa: F401
from . import label_semantic_roles  # noqa: F401
