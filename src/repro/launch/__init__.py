"""Launchers: mesh construction, train/serve drivers, compile cache, trace CLI."""
