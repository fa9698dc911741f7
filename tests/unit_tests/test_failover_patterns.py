"""Failover pattern library: every row classifies a realistic error
text to the right (category, scope) — the declarative equivalent of
the reference's FailoverCloudErrorHandlerV1/V2 blocklist mapping
(sky/backends/cloud_vm_ray_backend.py:395,522), tested row by row.
"""
import pytest

from skypilot_tpu import exceptions
from skypilot_tpu.provision import failover_patterns as fp

P = exceptions.ProvisionerError

# Each case: (cloud, code, message, expected_category, expected_scope).
GCP_CASES = [
    ('ZONE_RESOURCE_POOL_EXHAUSTED',
     'The zone does not have enough resources', P.CAPACITY, fp.ZONE),
    ('ZONE_RESOURCE_POOL_EXHAUSTED_WITH_DETAILS',
     'us-central1-a does not have enough resources available',
     P.CAPACITY, fp.ZONE),
    ('insufficientCapacity', '', P.CAPACITY, fp.ZONE),
    ('8', 'There is no more capacity in the zone "europe-west4-a"',
     P.CAPACITY, fp.ZONE),
    ('9', 'Insufficient reserved capacity. Contact customer support',
     P.CAPACITY, fp.ZONE),
    ('3', 'Cloud TPU received a bad request. update is not supported '
     'while in state PREEMPTED', P.CAPACITY, fp.ZONE),
    ('UNSUPPORTED_OPERATION', 'operation not supported', P.CAPACITY,
     fp.ZONE),
    ('RESOURCE_NOT_READY', 'resource not ready', P.TRANSIENT, fp.ZONE),
    ('429', 'RESOURCE_EXHAUSTED', P.CAPACITY, fp.ZONE),
    ('RESOURCE_NOT_FOUND', 'instance disappeared during provisioning',
     P.CAPACITY, fp.ZONE),
    ('RESOURCE_OPERATION_RATE_EXCEEDED', '', P.TRANSIENT, fp.ZONE),
    ('429', 'Quota exceeded for quota metric requests per minute',
     P.TRANSIENT, fp.ZONE),
    ('QUOTA_EXCEEDED', "Quota 'GPUS_ALL_REGIONS' exceeded. Limit: 1.0 "
     'globally.', P.QUOTA, fp.CLOUD),
    ('QUOTA_EXCEEDED', "Quota 'CPUS' exceeded. Limit: 24.0 in region "
     'us-west1.', P.QUOTA, fp.REGION),
    ('type.googleapis.com/google.rpc.QuotaFailure',
     "Quota 'TPUV2sPreemptiblePodPerProjectPerZoneForTPUAPI' exhausted. "
     'Limit 32 in zone europe-west4-a', P.QUOTA, fp.ZONE),
    ('VPC_NOT_FOUND', 'vpc skypilot-vpc not found', P.CONFIG, fp.CLOUD),
    ('SUBNET_NOT_FOUND_FOR_VPC', 'no subnet for region', P.CONFIG,
     fp.REGION),
    ('400', 'Requested disk size cannot be smaller than the image size '
     '(10 GB)', P.CONFIG, fp.ABORT),
    ('400', 'Invalid value for field machineType', P.CONFIG, fp.ABORT),
    ('400', "Machine type a3-highgpu-8g does not exist in zone "
     'us-west1-a', P.CONFIG, fp.ZONE),
    ('IAM_PERMISSION_DENIED', 'Policy update access denied.',
     P.PERMISSION, fp.CLOUD),
    ('403', 'Location us-east1-d is not found or access is unauthorized.',
     P.PERMISSION, fp.ZONE),
    ('403', 'Billing must be enabled for activation of service',
     P.PERMISSION, fp.CLOUD),
    ('403', 'Project has not accepted the Terms of Service', P.PERMISSION,
     fp.CLOUD),
    ('403', 'The caller lacks permission tpu.nodes.create', P.PERMISSION,
     fp.CLOUD),
    ('401', 'ACCESS_TOKEN_EXPIRED', P.PERMISSION, fp.CLOUD),
    ('503', 'backendError', P.TRANSIENT, fp.ZONE),
    ('503', 'invalid state, please retry', P.TRANSIENT, fp.ZONE),
]

AWS_CASES = [
    ('InsufficientInstanceCapacity', 'We currently do not have sufficient '
     'p4d.24xlarge capacity', P.CAPACITY, fp.ZONE),
    ('InsufficientHostCapacity', '', P.CAPACITY, fp.ZONE),
    ('InsufficientReservedInstanceCapacity', '', P.CAPACITY, fp.ZONE),
    ('InsufficientCapacityOnOutpost', '', P.CAPACITY, fp.ZONE),
    ('UnfulfillableCapacity', '', P.CAPACITY, fp.ZONE),
    ('SpotMaxPriceTooLow', 'Your Spot request price of 0.1 is lower than '
     'the minimum', P.CAPACITY, fp.ZONE),
    ('MarketCapacityOversubscribed', '', P.CAPACITY, fp.ZONE),
    ('Unsupported', 'The requested configuration is currently not '
     'supported in your requested Availability Zone', P.CAPACITY, fp.ZONE),
    ('MaxSpotInstanceCountExceeded', '', P.QUOTA, fp.REGION),
    ('InstanceLimitExceeded', 'You have requested more vCPU capacity than '
     'your current limit', P.QUOTA, fp.REGION),
    ('VcpuLimitExceeded', '', P.QUOTA, fp.REGION),
    ('VolumeLimitExceeded', '', P.QUOTA, fp.REGION),
    ('AddressLimitExceeded', '', P.QUOTA, fp.REGION),
    ('OptInRequired', 'You are not subscribed to this service',
     P.PERMISSION, fp.REGION),
    ('PendingVerification', 'Your account is currently being verified',
     P.PERMISSION, fp.CLOUD),
    ('UnauthorizedOperation', 'You are not authorized to perform this '
     'operation', P.PERMISSION, fp.CLOUD),
    ('AuthFailure', 'AWS was not able to validate the provided access '
     'credentials', P.PERMISSION, fp.CLOUD),
    ('InvalidClientTokenId', '', P.PERMISSION, fp.CLOUD),
    ('ExpiredToken', '', P.PERMISSION, fp.CLOUD),
    ('SignatureDoesNotMatch', '', P.PERMISSION, fp.CLOUD),
    ('InvalidAMIID.NotFound', 'The image id does not exist', P.CONFIG,
     fp.REGION),
    ('InvalidSubnetID.NotFound', '', P.CONFIG, fp.REGION),
    ('InvalidKeyPair.NotFound', '', P.CONFIG, fp.REGION),
    ('InvalidParameterValue', '', P.CONFIG, fp.ABORT),
    ('MissingParameter', '', P.CONFIG, fp.ABORT),
    ('RequestLimitExceeded', 'Request limit exceeded', P.TRANSIENT,
     fp.ZONE),
    ('Throttling', '', P.TRANSIENT, fp.ZONE),
    ('InternalError', '', P.TRANSIENT, fp.ZONE),
    ('ServiceUnavailable', '', P.TRANSIENT, fp.ZONE),
]

AZURE_CASES = [
    ('ZonalAllocationFailed', 'Allocation failed in the zone',
     P.CAPACITY, fp.ZONE),
    ('OverconstrainedZonalAllocationRequest', '', P.CAPACITY, fp.ZONE),
    ('SkuNotAvailable', 'The requested VM size Standard_ND96asr is not '
     'available in the current region', P.CAPACITY, fp.REGION),
    ('AllocationFailed', '', P.CAPACITY, fp.REGION),
    ('OverconstrainedAllocationRequest', '', P.CAPACITY, fp.REGION),
    ('SpotEvictedNotAvailable', '', P.CAPACITY, fp.REGION),
    ('VMStartTimedOut', '', P.CAPACITY, fp.REGION),
    ('LowPriorityQuotaExceeded', '', P.QUOTA, fp.REGION),
    ('QuotaExceeded', 'Operation could not be completed as it results in '
     'exceeding approved quota', P.QUOTA, fp.REGION),
    ('OperationNotAllowed', 'Operation results in exceeding quota limits '
     'of Core', P.QUOTA, fp.REGION),
    ('ReadOnlyDisabledSubscription', 'The subscription is disabled',
     P.PERMISSION, fp.CLOUD),
    ('SubscriptionNotRegistered', '', P.PERMISSION, fp.CLOUD),
    ('SubscriptionNotFound', '', P.PERMISSION, fp.CLOUD),
    ('ResourcePurchaseValidationFailed', '', P.PERMISSION, fp.CLOUD),
    ('RequestDisallowedByPolicy', '', P.PERMISSION, fp.CLOUD),
    ('DisallowedProvider', '', P.PERMISSION, fp.CLOUD),
    ('AuthorizationFailed', 'The client does not have authorization',
     P.PERMISSION, fp.CLOUD),
    ('InvalidAuthenticationToken', '', P.PERMISSION, fp.CLOUD),
    ('ExpiredAuthenticationToken', '', P.PERMISSION, fp.CLOUD),
    ('ClientAuthenticationError', '', P.PERMISSION, fp.CLOUD),
    ('ProvisioningDisabled', '', P.PERMISSION, fp.REGION),
    ('ImageNotFound', '', P.CONFIG, fp.ABORT),
    ('InvalidTemplateDeployment', '', P.CONFIG, fp.ABORT),
    ('InvalidParameter', '', P.CONFIG, fp.ABORT),
    ('ResourceGroupNotFound', '', P.CONFIG, fp.REGION),
    ('VMMarketplaceInvalidInput', '', P.CONFIG, fp.ABORT),
    ('TooManyRequests', '', P.TRANSIENT, fp.ZONE),
    ('InternalServerError', '', P.TRANSIENT, fp.ZONE),
    ('GatewayTimeout', '', P.TRANSIENT, fp.ZONE),
]

_ALL = ([('gcp',) + c for c in GCP_CASES] +
        [('aws',) + c for c in AWS_CASES] +
        [('azure',) + c for c in AZURE_CASES])


@pytest.mark.parametrize('cloud,code,message,category,scope', _ALL,
                         ids=[f'{c[0]}-{c[1][:40]}-{i}'
                              for i, c in enumerate(_ALL)])
def test_pattern_classification(cloud, code, message, category, scope):
    pat = fp.classify(cloud, code, message)
    assert pat is not None, 'expected a table match'
    assert (pat.category, pat.scope) == (category, scope)


def test_real_gce_machine_type_text_stays_zone_scoped():
    """The REAL GCE 400 text prefixes the zone-coverage miss with
    'Invalid value for field ...' — the abort row must not shadow the
    zone row for it."""
    text = ("Invalid value for field 'resource.machineType': "
            "'zones/us-west1-a/machineTypes/a3-highgpu-8g'. "
            "Machine type a3-highgpu-8g does not exist in zone "
            "us-west1-a.")
    pat = fp.classify('gcp', '400', text)
    assert (pat.category, pat.scope) == (P.CONFIG, fp.ZONE)


def test_aws_resource_count_exceeded_is_transient():
    """ResourceCountExceeded is an API-side throttle, not quota — it
    must not region-block (ordering vs the *LimitExceeded catch-all)."""
    pat = fp.classify('aws', 'ResourceCountExceeded', '')
    assert (pat.category, pat.scope) == (P.TRANSIENT, fp.ZONE)


def test_minimum_pattern_breadth():
    """The library must keep >=20 distinct classified shapes per major
    cloud."""
    assert len(fp.GCP_PATTERNS) >= 20
    assert len(fp.AWS_PATTERNS) >= 20
    assert len(fp.AZURE_PATTERNS) >= 20
    # And the cases above must actually exercise >=20 per cloud.
    assert len(GCP_CASES) >= 20
    assert len(AWS_CASES) >= 20
    assert len(AZURE_CASES) >= 20


def test_unknown_error_degrades_to_transient_zone():
    """Pattern misses fall to each cloud's PRODUCTION status-code
    fallback, which must walk on (transient/zone) for unknown shapes."""
    assert fp.classify('gcp', 'SOMETHING_NEW', 'never seen before') is None
    from skypilot_tpu.provision.aws import ec2_api
    from skypilot_tpu.provision.azure import arm_api
    from skypilot_tpu.provision.gcp import tpu_api
    for category, scope in (
            tpu_api._classify_error(500, 'SOMETHING_NEW'),
            ec2_api._classify_error('SomethingNew', 'never seen'),
            arm_api._classify_error('SomethingNew', 'never seen')):
        err = P('x', category=category, scope=scope)
        assert category == P.TRANSIENT
        assert not err.no_failover and not err.blocks_region \
            and not err.blocks_cloud


def test_scope_drives_error_flags():
    assert P('x', category=P.QUOTA, scope=fp.CLOUD).blocks_cloud
    assert P('x', category=P.CONFIG, scope=fp.REGION).blocks_region
    assert not P('x', category=P.CONFIG, scope=fp.REGION).no_failover
    assert P('x', category=P.CONFIG).no_failover  # default abort


def test_quota_body_with_resource_exhausted_status_region_blocks():
    """Real Google quota bodies carry status RESOURCE_EXHAUSTED next to
    the quota message — the quota row must win (region scope), not the
    bare capacity row."""
    body = ('{"error": {"code": 429, "message": "Quota '
            "'TPUSPerProjectPerRegion' exceeded. Limit: 32 in region "
            'europe-west4.", "status": "RESOURCE_EXHAUSTED"}}')
    pat = fp.classify('gcp', '429', body)
    assert (pat.category, pat.scope) == (P.QUOTA, fp.REGION)
    # The bare status with no quota text stays capacity/zone.
    pat = fp.classify('gcp', '429', 'RESOURCE_EXHAUSTED')
    assert (pat.category, pat.scope) == (P.CAPACITY, fp.ZONE)


K8S_CASES = [
    ('', '0/12 nodes are available: 12 Insufficient google.com/tpu. '
     'Unschedulable', P.CAPACITY, fp.ZONE),
    ('', 'FailedScheduling: No nodes are available', P.CAPACITY,
     fp.ZONE),
    ('', 'Pod was Evicted', P.CAPACITY, fp.ZONE),
    ('403', 'pods is forbidden: User cannot create resource',
     P.PERMISSION, fp.CLOUD),
    ('401', 'Unauthorized', P.PERMISSION, fp.CLOUD),
    ('403', 'exceeded quota: team-quota, requested: requests.cpu=64',
     P.QUOTA, fp.REGION),
    ('422', "Pod 'x' is invalid: spec.containers[0].image: "
     'Invalid value', P.CONFIG, fp.ABORT),
    ('400', 'admission webhook "policy.example.com" denied the request',
     P.CONFIG, fp.CLOUD),
    ('', 'Back-off pulling image: ImagePullBackOff', P.TRANSIENT,
     fp.ZONE),
    ('', 'InvalidImageName: invalid reference format', P.CONFIG,
     fp.ABORT),
    ('429', 'TooManyRequests: rate limited', P.TRANSIENT, fp.ZONE),
    ('500', 'etcdserver: request timed out', P.TRANSIENT, fp.ZONE),
]


@pytest.mark.parametrize('code,message,category,scope', K8S_CASES,
                         ids=[f'k8s-{i}' for i in range(len(K8S_CASES))])
def test_k8s_pattern_classification(code, message, category, scope):
    pat = fp.classify('kubernetes', code, message)
    assert pat is not None
    assert (pat.category, pat.scope) == (category, scope)
