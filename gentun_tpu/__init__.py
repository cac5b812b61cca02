"""gentun_tpu — TPU-native distributed genetic-algorithm search.

A brand-new framework with the capabilities of gentun (hyperparameter and
CNN-architecture search via genetic algorithms, distributed master/worker
fitness evaluation), designed TPU-first on JAX/Flax/XLA:

- deterministic, PRNG-threaded GA engine (``genes``, ``individuals``,
  ``populations``, ``algorithms``),
- Genetic-CNN fitness as a *masked supergraph*: every genome shares one
  compiled XLA program, and whole populations train as a single vmapped
  batch (``ops``, ``models``),
- multi-chip scaling via ``jax.sharding`` meshes — population-parallel ×
  data-parallel (``parallel``),
- a master/worker job broker over TCP with at-least-once redelivery, the
  RabbitMQ-equivalent control plane (``distributed``).

Public API mirrors the reference (``gentun/__init__.py`` [PUB]; SURVEY.md
§2.0 row 1): model-dependent names are re-exported defensively so a missing
optional dependency never breaks ``import gentun_tpu``.
"""

from .genes import (
    BinaryGene,
    ChoiceGene,
    FloatGene,
    GenomeSpec,
    IntGene,
    boosting_genome,
    deepseek_v2_genome,
    genetic_cnn_genome,
    lfm2_moe_genome,
    xgboost_genome,
)
from .individuals import (BoostingIndividual, DeepseekV2Individual, GeneticCnnIndividual, Individual, Lfm2MoeIndividual,
                          XgboostIndividual)
from .populations import GridPopulation, Population
from .algorithms import GeneticAlgorithm, RussianRouletteGA
from .algorithms_async import AsyncEvolution
from .surrogate import FitnessSurrogate, SurrogateGate
from . import telemetry  # noqa: F401  (zero-dependency; see docs/OBSERVABILITY.md)

__all__ = [
    "telemetry",
    "BinaryGene",
    "FloatGene",
    "IntGene",
    "ChoiceGene",
    "GenomeSpec",
    "genetic_cnn_genome",
    "boosting_genome",
    "xgboost_genome",
    "lfm2_moe_genome",
    "deepseek_v2_genome",
    "Individual",
    "GeneticCnnIndividual",
    "BoostingIndividual",
    "XgboostIndividual",
    "Lfm2MoeIndividual",
    "DeepseekV2Individual",
    "Population",
    "GridPopulation",
    "GeneticAlgorithm",
    "RussianRouletteGA",
    "AsyncEvolution",
    "FitnessSurrogate",
    "SurrogateGate",
]

__version__ = "0.6.0"  # keep in sync with pyproject.toml

# Fitness models pull in jax/flax/sklearn; keep them optional at import time,
# matching the reference's try/except around model imports (SURVEY.md §2.0
# row 1: missing xgboost/keras must not break the package import).
try:  # pragma: no cover - exercised implicitly
    from .models.cnn import GeneticCnnModel  # noqa: F401

    __all__.append("GeneticCnnModel")
except ImportError:  # pragma: no cover
    pass

try:  # pragma: no cover
    from .models.lfm2_moe import Lfm2MoeModel  # noqa: F401

    __all__.append("Lfm2MoeModel")
except ImportError:  # pragma: no cover
    pass

try:  # pragma: no cover
    from .models.boosting import BoostingModel  # noqa: F401

    __all__.append("BoostingModel")
except ImportError:  # pragma: no cover
    pass

try:  # pragma: no cover
    from .distributed.server import DistributedPopulation, DistributedGridPopulation  # noqa: F401
    from .distributed.client import GentunClient  # noqa: F401
    from .distributed.broker import GatherTimeout, JobBroker, JobFailed  # noqa: F401

    __all__ += [
        "DistributedPopulation",
        "DistributedGridPopulation",
        "GentunClient",
        "JobBroker",
        "JobFailed",
        "GatherTimeout",
    ]
except ImportError:  # pragma: no cover
    pass
