"""Deployments that need more than ``harness.deploy.Deployment`` gives:
a class each, named by dotted path in a configuration's file."""
