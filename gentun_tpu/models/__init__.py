"""Fitness models: the compute layer (SURVEY.md §2.0 rows 8-9).

``GentunModel`` is the ABC; ``GeneticCnnModel`` is the TPU hot path;
the boosting control path has two interchangeable backends —
``XgboostModel`` (the reference's exact ``xgb.cv`` semantics, used
automatically whenever xgboost is importable) and ``BoostingModel``
(sklearn gradient boosting, the fallback in this xgboost-less
environment, SURVEY.md §2.1).  Both accept the same
``additional_parameters``, so individuals and wire payloads are
backend-agnostic.
"""

import logging

from .generic import GentunModel

__all__ = ["GentunModel", "default_boosting_model"]

_backend_logged = False


def default_boosting_model():
    """The boosting fitness backend for this environment.

    Fallback chain: real xgboost (``models/xgboost.py`` — all 11 reference
    genes live) when importable, else the sklearn translation
    (``models/boosting.py`` — 7 of 11 live, warned loudly).

    The selection is logged once per process (ADVICE r3): in a distributed
    search a mixed fleet would otherwise silently score one generation with
    two different estimators; workers also advertise the backend name in
    their broker handshake so the MASTER warns on heterogeneity
    (``distributed/broker.py``).
    """
    global _backend_logged
    from .xgboost import XgboostModel, xgboost_available

    if xgboost_available():
        selected = XgboostModel
    else:
        from .boosting import BoostingModel

        selected = BoostingModel
    if not _backend_logged:
        _backend_logged = True
        logging.getLogger("gentun_tpu").info(
            "boosting fitness backend: %s", selected.__name__
        )
    return selected

try:  # jax/flax may be absent in minimal installs
    from .cnn import GeneticCnnModel, MaskedGeneticCnn  # noqa: F401

    __all__ += ["GeneticCnnModel", "MaskedGeneticCnn"]
except ImportError:  # pragma: no cover
    pass

try:
    from .lfm2_moe import Lfm2MoeModel  # noqa: F401

    __all__ += ["Lfm2MoeModel"]
except ImportError:  # pragma: no cover
    pass

try:
    from .boosting import BoostingModel  # noqa: F401

    __all__ += ["BoostingModel"]
except ImportError:  # pragma: no cover
    pass
