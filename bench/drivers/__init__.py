"""Simulator drivers, one module per kind of traffic."""
