"""LightGBM-TPU: a TPU-native gradient-boosted decision tree framework.

A from-scratch JAX/XLA/Pallas re-design with the capabilities of the
reference LightGBM (v2.2.4): histogram-based leaf-wise GBDT/DART/GOSS/RF,
the full objective/metric families, categorical optimal splits, and
data-/feature-/voting-parallel learners mapped onto XLA collectives over a
TPU device mesh.
"""
from .utils.backend import configure_compile_cache as _configure_compile_cache

# before anything below can compile a program
_configure_compile_cache()

from .basic import Booster, Dataset  # noqa: E402,F401
from .callback import (early_stopping, print_evaluation,  # noqa: E402,F401
                       record_evaluation, reset_parameter)
from .config import Config  # noqa: E402,F401
from .engine import cv, train  # noqa: E402,F401
from .plotting import (create_tree_digraph, plot_importance,  # noqa: E402,F401
                       plot_metric, plot_tree)
from .sklearn import (LGBMClassifier, LGBMModel,  # noqa: E402,F401
                      LGBMRanker, LGBMRegressor)
from .utils import log  # noqa: E402,F401

__version__ = "2.2.4.tpu0"

__all__ = ["Config", "Dataset", "Booster", "train", "cv", "log",
           "early_stopping", "print_evaluation", "record_evaluation",
           "reset_parameter",
           "plot_importance", "plot_metric", "plot_tree",
           "create_tree_digraph", "LGBMModel", "LGBMClassifier",
           "LGBMRegressor", "LGBMRanker"]
