"""The code that drives each system under test, one module per system
(``config["system"]``)."""
