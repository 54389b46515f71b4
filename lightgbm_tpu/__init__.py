"""lightgbm_tpu: a TPU-native gradient boosting framework.

A from-scratch re-design of LightGBM (v2.1.1 feature surface) for
JAX/XLA on TPU: HBM-resident packed bin matrix, MXU one-hot-matmul
histograms, fully-jitted leaf-wise tree growth, XLA-collective
distributed training.  User API mirrors the reference python package
(lgb.train / Dataset / Booster / sklearn wrappers).
"""
import time as _time
_t_import = _time.perf_counter()     # the "import" set-up stage starts here
from . import telemetry              # (stdlib only: it holds the stage's clock)
_rss_import = telemetry.read_rss_mb()
from .basic import Dataset, Booster
from .config import Config
from .engine import train, cv, CVBooster
from .utils.log import Log, LightGBMError
from .callback import (early_stopping, print_evaluation, record_evaluation,
                       reset_parameter, telemetry_snapshot)
from .telemetry import TELEMETRY
_t_sklearn = _time.perf_counter()
from .sklearn import LGBMModel, LGBMRegressor, LGBMClassifier, LGBMRanker
_t_sklearn = _time.perf_counter() - _t_sklearn
from . import plotting
from .plotting import (plot_importance, plot_metric, plot_tree,
                       create_tree_digraph)

__version__ = "0.1.0"

__all__ = ["Dataset", "Booster", "Config", "train", "cv", "CVBooster", "Log",
           "LightGBMError", "early_stopping", "print_evaluation",
           "record_evaluation", "reset_parameter", "telemetry_snapshot",
           "telemetry", "TELEMETRY", "LGBMModel",
           "LGBMRegressor", "LGBMClassifier", "LGBMRanker",
           "plot_importance", "plot_metric", "plot_tree",
           "create_tree_digraph", "__version__"]

# published as setup_import_ms / setup_import_sklearn_ms by the first
# configure that turns counters on (docs/OBSERVABILITY.md)
telemetry.note_import(_t_import, _time.perf_counter(), _rss_import,
                      _t_sklearn)
