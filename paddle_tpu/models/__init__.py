"""Model zoo with the reference's benchmark/book models
(reference: benchmark/fluid/models/*, python/paddle/fluid/tests/book/*),
built on the paddle_tpu layers API.

Each module exposes the network builder plus a ``get_model(...)`` helper
returning ``(avg_cost, aux-metric-or-None, feed_vars)`` for training
scripts.
"""
from . import mnist  # noqa: F401
from . import vgg  # noqa: F401
from . import resnet  # noqa: F401
from . import stacked_lstm  # noqa: F401
from . import transformer  # noqa: F401
from . import word2vec  # noqa: F401
from . import deepfm  # noqa: F401
from . import se_resnext  # noqa: F401
from . import srl  # noqa: F401
from . import seq2seq  # noqa: F401
from . import recommender  # noqa: F401
from . import ssd  # noqa: F401
from . import fit_a_line  # noqa: F401
from . import mobilenet  # noqa: F401
