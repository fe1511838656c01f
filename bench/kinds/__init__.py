"""Deployment kinds, one module each, found by the configuration's
``deployment.kind``."""
