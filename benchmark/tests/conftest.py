import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                                   # rehearsal.py
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))  # the repo
