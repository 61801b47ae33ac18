"""The plain reference: straightforward numpy float64 implementations of
what the cells compute, independent of lightgbm_tpu, that decide `correct`."""
