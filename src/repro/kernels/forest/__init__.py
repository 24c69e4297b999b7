from .ops import PallasForest, forest_predict
from .ref import forest_predict_ref

__all__ = ["PallasForest", "forest_predict", "forest_predict_ref"]
