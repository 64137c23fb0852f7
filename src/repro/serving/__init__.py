from repro.serving.batching import EndpointBatcher, pad_batch  # noqa: F401
from repro.serving.datastore import TieredDatastore  # noqa: F401
from repro.serving.engine import ModelEndpoint, ServingEngine, WarmBudget  # noqa: F401
from repro.serving.executor import Executor  # noqa: F401
from repro.serving.weights import WeightStore  # noqa: F401
