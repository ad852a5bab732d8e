"""The benchmark's own machinery: manifest, traffic, weights, the trace
reduction, the reducers of per-layer metrics, the comparison behind `correct`
and the result line.  Nothing here names a model width, a length law or a
rate: those live in `configs/`, `traffic/` and `layer_metrics/`."""
