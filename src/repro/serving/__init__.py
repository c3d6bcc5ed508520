from repro.serving.cache import DenseCache, ServeCache
from repro.serving.plane import ServingPlane
from repro.serving.predictor import make_serve_step
from repro.serving.registry import Scenario, ScenarioRegistry
from repro.serving.router import RowRouter
from repro.serving.scheduler import DEFAULT_BUCKETS, PredictScheduler

__all__ = [
    "DEFAULT_BUCKETS", "DenseCache", "PredictScheduler", "RowRouter",
    "Scenario", "ScenarioRegistry", "ServeCache", "ServingPlane",
    "make_serve_step",
]
